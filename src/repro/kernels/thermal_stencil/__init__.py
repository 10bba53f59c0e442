from repro.kernels.thermal_stencil.ops import apply_operator  # noqa: F401
