"""repro: Partitioning Uncertain Workflows (Huberman & Chua, 2015) as a
JAX scheduler: stage splits solved on the frontier kernel, served by a
continuous-batching engine. See README.md."""
__version__ = "1.0.0"
