"""Launchers: mesh construction, multi-pod dry-run, train, serve.

NOTE: do not import repro.launch.dryrun from library code — it sets
XLA_FLAGS for 512 placeholder devices at import time (dry-run only).
"""
from repro.launch.mesh import make_mesh, make_production_mesh, make_rules  # noqa: F401
