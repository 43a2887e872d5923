"""Public wrappers for the Pallas kernels, with a jnp fallback when the
problem exceeds the kernels' VMEM-resident assumptions (or when
``REPRO_DISABLE_PALLAS=1``).  Tests sweep them against ``ref.py``.

The CPQ query path calls no Pallas kernel: the TPU compiler refuses the
per-lane vector gathers a sorted-intersection or expansion kernel needs
("Only 2D gather is supported"), so ``core.backend`` runs those
operators as XLA programs (``core.relational``).

The VMEM ceiling (:func:`vmem_words`) is process-wide state fed from the
device cost table (``core.costmodel.activate``): env
``REPRO_VMEM_WORDS`` wins, then a table override installed by
:func:`set_vmem_words_override`, then a per-backend default.
"""

from __future__ import annotations

import os

import jax

from . import fingerprint as _fp
from . import ref
from . import segment_softmax as _ss

# Fallback VMEM-residency ceiling for the broadcast operands (int32
# words) when neither the env override nor the backend probe decides;
# beyond the ceiling the ops fall back to the XLA path, which tiles
# through HBM.
_DEFAULT_VMEM_WORDS = 1_000_000

# TPU cores carry ~16 MiB VMEM; budget half of it for the broadcast
# operands (the other half covers the blocked operand, accumulators and
# double-buffering) -> 8 MiB / 4 B.
_TPU_VMEM_WORDS = (8 * 1024 * 1024) // 4

_vmem_override: int | None = None  # set_vmem_words_override (cost table)
_vmem_probed: int | None = None  # cached backend probe


def set_vmem_words_override(words: int | None) -> None:
    """Install (or with None clear) a cost-table-provided VMEM ceiling.
    The ``REPRO_VMEM_WORDS`` env var still wins — it is the operator's
    explicit knob."""
    global _vmem_override
    _vmem_override = None if words is None else int(words)


def vmem_words() -> int:
    """The broadcast-operand residency ceiling, in int32 words.

    Resolution order: ``REPRO_VMEM_WORDS`` env (read live, so tests can
    monkeypatch it per-case), then the installed cost-table override,
    then a cached per-backend default (TPU budgets half a core's ~16 MiB
    VMEM; CPU/GPU interpret or re-tile, so the conservative historical
    ceiling stands).
    """
    env = os.environ.get("REPRO_VMEM_WORDS")
    if env:
        return int(env)
    if _vmem_override is not None:
        return _vmem_override
    global _vmem_probed
    if _vmem_probed is None:
        _vmem_probed = (_TPU_VMEM_WORDS if jax.default_backend() == "tpu"
                        else _DEFAULT_VMEM_WORDS)
    return _vmem_probed


def _pallas_enabled() -> bool:
    return os.environ.get("REPRO_DISABLE_PALLAS", "0") != "1"


def fingerprint_rows(cols: tuple, salt: int = 0):
    if not _pallas_enabled():
        return ref.fingerprint_rows(cols, salt)
    return _fp.fingerprint_rows(tuple(cols), salt=salt)


def segment_softmax(scores, segment_ids, num_segments, eps: float = 1e-9):
    e = scores.shape[0]
    if (not _pallas_enabled() or num_segments * scores.shape[1] > vmem_words()
            or e % min(512, e) != 0):
        return ref.segment_softmax(scores, segment_ids, num_segments, eps)
    return _ss.segment_softmax(scores, segment_ids, num_segments, eps=eps)
