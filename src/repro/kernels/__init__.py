"""Pallas TPU kernels, each with an ops.py wrapper (+ jnp fallback) and a
ref.py pure-jnp oracle: fingerprint (signature sets), segment_softmax
(GNN substrate).  The CPQ query path runs no kernel of this package."""
