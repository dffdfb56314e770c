"""Launchers: production mesh, multi-pod dry-run, training and serving drivers."""
from repro.launch.mesh import make_host_mesh, make_production_mesh  # noqa: F401
