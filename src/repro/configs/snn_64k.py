"""Production-scale SNN core: 65,536 neurons, all-to-all fabric.

The paper's architecture scaled to the point where the synapse matrix
(64k x 64k = 4.3G synapses, 16 GiB in f32) must shard across the mesh --
the "universal interconnect" as a distributed system (DESIGN.md §15).
``snn_mesh=4`` partitions the fabric by destination columns over a
4-device ``("model",)`` mesh -- one TPU v5e host's 4 chips, 4 GiB of
weights on each of their 16 GB; the implicit all-to-all (``c=None``)
means no second mask matrix ever exists.  Used
by the SNN scaling benchmark's sharded section and runnable from the
serve CLI (``python -m repro.launch.serve --arch snn-64k --smoke``).
"""
from repro.configs import register
from repro.configs.base import ArchBundle, ModelConfig, ParallelConfig

FULL = ModelConfig(
    name="snn-64k",
    family="snn",
    n_neurons=65536,
    layer_sizes=(),        # free-form all-to-all, not layered
    n_ticks=8,
    snn_mode="fixed_leak",
    snn_mesh=4,            # shard the fabric over 4 chips (DESIGN.md §15)
    dtype="float32",
    source="DESIGN.md §4 scale-up of paper §II.D",
)

SMOKE = ModelConfig(
    name="snn-64k-smoke",
    family="snn",
    n_neurons=256,
    layer_sizes=(),
    n_ticks=8,
    snn_mode="fixed_leak",
    snn_mesh=2,            # exercise the sharded path at smoke scale
    head_pad=1,
    dtype="float32",
)


@register("snn-64k")
def bundle() -> ArchBundle:
    return ArchBundle(model=FULL, smoke=SMOKE, parallel={"*": ParallelConfig()})
