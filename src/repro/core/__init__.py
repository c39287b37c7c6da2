"""The control plane: profiles, planner, simulator and adapter, in numpy.

``predictor`` (the LSTM demand predictor, in JAX) is imported by name only,
so that importing the planner imports no JAX."""
from repro.core import (accuracy, adapter, baselines, optimizer,  # noqa: F401
                        paper_profiles, pipeline, profiler, queueing,
                        simulator, trace)
