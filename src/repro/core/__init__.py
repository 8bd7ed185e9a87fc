"""Core: the paper's contribution — combined spatial + temporal blocking."""
from repro.core.blocking import BlockGeometry
from repro.core.boundary import BoundaryCondition
from repro.core.engine import blocked_superstep
from repro.core.perf_model import Device, Prediction, autotune, predict
from repro.core.stencils import (DIFFUSION2D, DIFFUSION3D, HOTSPOT2D,
                                 HOTSPOT3D, STENCILS, Stencil, default_coeffs,
                                 make_box, make_star)

__all__ = [
    "BlockGeometry", "BoundaryCondition", "blocked_superstep", "Device",
    "Prediction", "autotune", "predict", "DIFFUSION2D", "DIFFUSION3D",
    "HOTSPOT2D", "HOTSPOT3D", "STENCILS", "Stencil", "default_coeffs",
    "make_box", "make_star",
]
