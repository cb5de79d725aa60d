# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

# Default kernel tile geometry: it = row-tile height, dt = diagonal-tile
# width. These live HERE (not in ops.py) so the planner (core.plan), the
# kernel wrappers (kernels.ops), the roofline model (launch.roofline) and
# the benchmarks all derive the same numbers without pulling the Pallas
# stack in — `repro.kernels` itself imports nothing. Every bytes/cell or
# roofline figure quoted against "the kernel" must use these defaults (the
# benches once modeled it=512/dt=32 while the kernel ran 256/8).
# 1024 x 64 because the compiled kernel pays a fixed cost per grid step:
# on a TPU v5e at seismology-64k it swept about 6x faster than 256 x 8,
# the fastest of the tiles tried (256x8, 512x32, 512x64, 512x128).
DEFAULT_IT = 1024
DEFAULT_DT = 64


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place the Pallas execution mode is decided: `None` (every
    entry's default) runs the interpreter only on the CPU backend, and the
    compiled Mosaic kernel everywhere else. An explicit bool is honored."""
    if interpret is None:
        import jax

        return jax.default_backend() == "cpu"
    return bool(interpret)
