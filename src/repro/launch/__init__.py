"""Launchers: mesh construction, multi-pod dry-run, train/serve CLIs.

NOTE: ``repro.launch.dryrun`` sets XLA_FLAGS at import — only run it as the
process entry point (``python -m repro.launch.dryrun``); do not import it
here or from library code.
"""
from .mesh import make_host_mesh, make_production_mesh, mesh_axes

__all__ = ["make_host_mesh", "make_production_mesh", "mesh_axes"]
