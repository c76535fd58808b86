"""Seeded graph generators, one file per family, each with
``make(graph: dict, seed: int) -> (n, src, dst, w)`` (numpy arrays,
float32 weights > 0, no self-loops)."""
