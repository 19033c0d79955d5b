"""Sophia-JAX: production-scale reproduction of 'Sophia: A Scalable
Stochastic Second-order Optimizer for Language Model Pre-training'
(Liu, Li, Hall, Liang, Ma — ICLR 2024) as a multi-pod JAX framework.

Subpackages: core (the optimizer), models (10-arch zoo), distributed
(sharding/EP/compression), train, serve, kernels (Pallas), configs, launch.
"""
from pathlib import Path

__version__ = "1.0.0"

# the checkout root (src/repro/__init__.py -> ../..): run-time caches that
# are not placed from outside live under it (gitignored)
CHECKOUT_DIR = Path(__file__).resolve().parents[2]
