"""Per-kernel microbenchmarks: Pallas (interpret on CPU / compiled on
TPU) vs the jnp reference path.  On CPU the relative numbers reflect
interpret-mode overhead — the correctness contract is what CI checks."""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import emit, timeit, write_json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as JSON")
    args, _ = ap.parse_known_args()
    rng = np.random.default_rng(0)

    # fingerprint: 2-column mix at build sizes
    n = 1 << 15
    cols = tuple(jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32))
                 for _ in range(2))
    f_k = jax.jit(lambda a, b: ops.fingerprint_rows((a, b), 3))
    f_r = jax.jit(lambda a, b: ref.fingerprint_rows((a, b), 3))
    jax.block_until_ready(f_k(*cols))
    jax.block_until_ready(f_r(*cols))
    emit(f"kernels/fingerprint/{n}/pallas",
         timeit(lambda: jax.block_until_ready(f_k(*cols))), "")
    emit(f"kernels/fingerprint/{n}/jnp_ref",
         timeit(lambda: jax.block_until_ready(f_r(*cols))), "")

    # segment_softmax at GNN edge sizes
    e, d, nseg = 1 << 14, 8, 1 << 10
    scores = jnp.asarray(rng.normal(0, 1, (e, d)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, nseg, e)).astype(np.int32))
    f_k = jax.jit(lambda s, g: ops.segment_softmax(s, g, nseg))
    f_r = jax.jit(lambda s, g: ref.segment_softmax(s, g, nseg))
    f_k(scores, seg).block_until_ready()
    f_r(scores, seg).block_until_ready()
    emit(f"kernels/segment_softmax/{e}x{d}/pallas",
         timeit(lambda: f_k(scores, seg).block_until_ready()), "")
    emit(f"kernels/segment_softmax/{e}x{d}/jnp_ref",
         timeit(lambda: f_r(scores, seg).block_until_ready()), "")

    jax.clear_caches()

    if args.json:
        write_json(args.json, bench="bench_kernels")


if __name__ == "__main__":
    main()
