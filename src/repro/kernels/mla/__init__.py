from repro.kernels.mla import ops, ref  # noqa: F401
