"""Launchers: mesh construction, multi-pod dry-run, train/serve drivers.

NOTE: do NOT import .dryrun here — it sets XLA_FLAGS at import time and
must only ever be imported as the program entry point.
"""

from .mesh import HW, make_local_mesh, make_mesh, make_production_mesh

__all__ = ["HW", "make_local_mesh", "make_mesh", "make_production_mesh"]
