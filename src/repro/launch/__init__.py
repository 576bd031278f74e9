"""Launch layer: the serving CLI, ``python -m repro.launch.serve``."""
