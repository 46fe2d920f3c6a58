"""The multi-tenant SNN serving fabric: one resident datapath, S slots.

``n_neurons`` here is the *fabric* size ``n_max`` -- every tenant network
is zero-padded onto it (padded neurons carry an unreachable threshold, so
they never spike and never learn). ``n_ticks`` is the per-request tick
budget ceiling; requests may ask for less and are masked at decode.
"""
import dataclasses

from repro.configs import register
from repro.configs.base import ArchBundle, ModelConfig, ParallelConfig

FULL = ModelConfig(
    name="snn-serve",
    family="snn",
    n_neurons=74,            # the paper's fabric, serving many tenants
    n_ticks=32,
    snn_mode="fixed_leak",
    dtype="float32",
    source="paper §II + multi-tenant serving (NeuroCoreX / low-end-FPGA time-sharing)",
)

SMOKE = dataclasses.replace(FULL, name="snn-serve-smoke", n_neurons=24, n_ticks=12)


@register("snn")
def bundle() -> ArchBundle:
    return ArchBundle(model=FULL, smoke=SMOKE, parallel={"*": ParallelConfig()})
