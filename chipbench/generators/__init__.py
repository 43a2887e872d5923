"""Graph generators, one module per name.  Each ``generate(cfg, rng)``
returns ``(n_vertices, n_labels, edges)``: ``edges`` is an (m, 3) int64
array of (src, dst, base label) before the inverse closure."""
