"""Serving data plane: request records, batching and the JAX stage engine.

Submodules are imported by name; the planner imports ``request`` without
pulling in JAX through ``engine``."""
