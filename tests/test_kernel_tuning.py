"""The kernel wrappers' VMEM ceiling: derived (env > cost-table
override > backend default), with a Pallas -> XLA fallback boundary that
must be invisible in the answers.  Separate from test_kernels.py so
these run without hypothesis."""

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


class TestVmemBoundary:
    def test_vmem_words_resolution_order(self, monkeypatch):
        monkeypatch.delenv("REPRO_VMEM_WORDS", raising=False)
        ops.set_vmem_words_override(None)
        base = ops.vmem_words()
        assert base >= 1_000_000  # never below the historical ceiling
        ops.set_vmem_words_override(4096)
        assert ops.vmem_words() == 4096
        monkeypatch.setenv("REPRO_VMEM_WORDS", "512")  # env always wins
        assert ops.vmem_words() == 512
        monkeypatch.delenv("REPRO_VMEM_WORDS")
        ops.set_vmem_words_override(None)
        assert ops.vmem_words() == base

    def test_segment_softmax_bit_identical_at_exact_ceiling(self,
                                                            monkeypatch):
        rng = np.random.default_rng(5)
        e, d, n = 512, 4, 64
        scores = jnp.asarray(rng.normal(0, 2, (e, d)).astype(np.float32))
        seg = jnp.asarray(np.sort(rng.integers(0, n, e)).astype(np.int32))
        words = n * d  # the wrapper's residency formula
        # at the ceiling: kernel path (the guard is strict >)
        monkeypatch.setenv("REPRO_VMEM_WORDS", str(words))
        at = np.asarray(ops.segment_softmax(scores, seg, n))
        # one word less: the XLA reference
        monkeypatch.setenv("REPRO_VMEM_WORDS", str(words - 1))
        below = np.asarray(ops.segment_softmax(scores, seg, n))
        np.testing.assert_allclose(at, below, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(
            below, np.asarray(ref.segment_softmax(scores, seg, n)))
