"""The Potjans-Diesmann cortical microcircuit at full scale.

Potjans & Diesmann 2014, *Cerebral Cortex* 24(3):785-806,
doi:10.1093/cercor/bhs358, with the parameters of NEST's reference
implementation (``pynest/examples/Potjans_2014/network_params.py`` and
``sim_params.py``): 77,169 ``iaf_psc_exp`` neurons in 8 populations,
298,880,968 synapses drawn ``fixed_total_number`` from the published
8x8 connection probabilities, per-synapse normal weights and delays,
Poisson background at 8 Hz times each population's external in-degree,
dt = 0.1 ms.

It runs through the normal path: :func:`engine_options` gives
``TickEngine(EngineOptions(mode="psc_exp", backend="event",
event_dispatch="fan_out"))``, the synapses live in a resident
:class:`~repro.core.connectivity.FanOut` built from a synapse list, the
delay ring is the state's ``delay_buf`` and the background is drawn on
the device (:class:`~repro.core.network_types.PoissonDrive`).

``scale`` below 1 (CPU tests) scales every population and every
projection's synapse count alike -- in-degrees, widths, weights and
delays stay as published.  Departures from NEST, all deliberate:

* weights sit on a dyadic grid of :data:`WEIGHT_QUANTUM` pA (integer
  levels, as the paper's integer weight registers), at most 1/32 pA off,
  so every sum of them is exact in float32 in any order;
* delays are whole ticks in ``[1, MAX_DELAY]`` (the ring's depth);
  normal draws below the 0.1 ms resolution or above ``MAX_DELAY`` ticks
  are redrawn (NEST redraws only below);
* no thalamic input (the published default), Poisson rather than DC
  background, initial membranes normal(-58, 10) mV (the "original"
  option).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

POPULATIONS = ("L23E", "L23I", "L4E", "L4I", "L5E", "L5I", "L6E", "L6I")
FULL_SIZES = (20683, 5834, 21915, 5479, 4850, 1065, 14395, 2948)
# Connection probabilities, rows = target population, columns = source.
CONN_PROBS = np.array([
    [0.1009, 0.1689, 0.0437, 0.0818, 0.0323, 0.0, 0.0076, 0.0],
    [0.1346, 0.1371, 0.0316, 0.0515, 0.0755, 0.0, 0.0042, 0.0],
    [0.0077, 0.0059, 0.0497, 0.135, 0.0067, 0.0003, 0.0453, 0.0],
    [0.0691, 0.0029, 0.0794, 0.1597, 0.0033, 0.0, 0.1057, 0.0],
    [0.1004, 0.0622, 0.0505, 0.0057, 0.0831, 0.3726, 0.0204, 0.0],
    [0.0548, 0.0269, 0.0257, 0.0022, 0.06, 0.3158, 0.0086, 0.0],
    [0.0156, 0.0066, 0.0211, 0.0166, 0.0572, 0.0197, 0.0396, 0.2252],
    [0.0364, 0.001, 0.0034, 0.0005, 0.0277, 0.008, 0.0658, 0.1443]])
K_EXT = (1600, 1500, 2100, 1900, 2000, 1900, 2900, 2100)
BG_RATE_HZ = 8.0
NEURON = dict(c_m=250.0, tau_m=10.0, tau_syn=0.5, t_ref=2.0, e_l=-65.0,
              v_th=-50.0, v_reset=-65.0)
V0_MEAN, V0_STD = -58.0, 10.0          # mV, the "original" option
PSP_MEAN = 0.15                        # mV, excitatory
WEIGHT_REL_STD = 0.1
G = -4.0                               # inhibitory / excitatory weight
L23E_FROM_L4E = 2.0                    # the doubled L4E -> L2/3E weight
DELAY_MEAN = (1.5, 0.75)               # ms, excitatory / inhibitory source
DELAY_REL_STD = 0.5
DT = 0.1                               # ms per tick
MAX_DELAY = 64                         # ring depth: delays 1..64 ticks
WEIGHT_QUANTUM = 2.0 ** -4             # pA
READ_BLOCK = 256                       # fan_out reads per block (k)
FANOUT_WINDOW = 512                    # fan-out entries per read


def psc_per_psp(c_m: float, tau_m: float, tau_syn: float) -> float:
    """pA of PSC amplitude per mV of PSP peak (NEST's
    ``postsynaptic_potential_to_current``)."""
    sub = 1.0 / (tau_syn - tau_m)
    pre = tau_m * tau_syn / c_m * sub
    frac = (tau_m / tau_syn) ** sub
    return 1.0 / (pre * (frac ** tau_m - frac ** tau_syn))


def quantize(w):
    """Weights onto the dyadic grid (pA, round half to even)."""
    return np.round(np.asarray(w, np.float64) / WEIGHT_QUANTUM) \
        * WEIGHT_QUANTUM


@dataclasses.dataclass(frozen=True)
class Microcircuit:
    """The microcircuit at ``scale`` (1.0 = the published model)."""

    scale: float = 1.0

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(round(n * self.scale)) for n in FULL_SIZES)

    @property
    def n(self) -> int:
        return sum(self.sizes)

    @property
    def pop_starts(self) -> Tuple[int, ...]:
        return tuple(int(x) for x in np.concatenate(
            [[0], np.cumsum(self.sizes)]))

    def synapse_counts(self) -> np.ndarray:
        """``K[target, source]``: synapses per projection, NEST's
        ``num_synapses_from_conn_probs`` for the full model, scaled."""
        full = np.asarray(FULL_SIZES, np.float64)
        prod = np.outer(full, full)
        k = np.log(1.0 - CONN_PROBS) / np.log((prod - 1.0) / prod)
        return np.round(k * self.scale).astype(np.int64)

    def weight_means(self) -> np.ndarray:
        """``w[target, source]`` mean PSC amplitudes in pA (unquantized)."""
        w_e = psc_per_psp(NEURON["c_m"], NEURON["tau_m"],
                          NEURON["tau_syn"]) * PSP_MEAN
        w = np.tile([w_e, G * w_e] * 4, (8, 1))
        w[0, 2] *= L23E_FROM_L4E
        return w

    def external_weight(self) -> float:
        """The background's PSC per event, on the grid."""
        return float(quantize(self.weight_means()[0, 0]))

    def lam(self) -> np.ndarray:
        """``(n,)`` float32 background events per neuron per tick."""
        per_pop = BG_RATE_HZ * np.asarray(K_EXT, np.float64) * DT / 1000.0
        return np.repeat(per_pop, self.sizes).astype(np.float32)

    def lif_params(self):
        from repro.core.lif import LIFParams

        return LIFParams.psc_exp(self.n, dt=DT, **NEURON)

    def poisson_drive(self, key):
        """The background, drawn on the device from ``key`` (raw
        ``uint32[2]``) and the absolute tick."""
        import jax.numpy as jnp

        from repro.core.network_types import PoissonDrive

        return PoissonDrive(
            key=key, lam=jnp.asarray(self.lam()),
            weight=jnp.full((self.n,), self.external_weight(), jnp.float32))

    def params(self, key):
        """:class:`SNNParams` of the fabric: no dense ``w``, the LIF
        propagators and the Poisson drive (the synapses live in the
        :class:`FanOut` passed beside them)."""
        import jax.numpy as jnp

        from repro.core.network_types import SNNParams

        return SNNParams(w=None, c=None,
                         w_in=jnp.zeros((0, self.n), jnp.float32),
                         lif=self.lif_params(), drive=self.poisson_drive(key))

    def initial_state(self, key):
        """Membranes normal(V0_MEAN, V0_STD) mV (held relative to E_L),
        currents, refractory counters and the ring at zero, tick 0."""
        import dataclasses as dc

        import jax
        import jax.numpy as jnp

        from repro.core.network_types import SNNState

        st = SNNState.zeros((), self.n, max_delay=MAX_DELAY, current=True)
        v0 = (V0_MEAN - NEURON["e_l"]) + V0_STD * jax.random.normal(
            key, (self.n,), jnp.float32)
        return dc.replace(st, lif=dc.replace(st.lif, v=v0))

    def fan_out(self, count, blocks, window: Optional[int] = None):
        """The resident :class:`~repro.core.connectivity.FanOut`, built
        on the device from synapse-list blocks (``count``: each source's
        out-degree), read ``window`` entries at a time."""
        from repro.core import connectivity

        return connectivity.build_fan_out(
            count, self.pop_starts, blocks,
            window=FANOUT_WINDOW if window is None else window)

    def engine_options(self, *, k: Optional[int] = None,
                       telemetry: bool = True):
        from repro.core.engine import EngineOptions

        return EngineOptions(
            mode="psc_exp", backend="event", event_dispatch="fan_out",
            event_k_active=READ_BLOCK if k is None else k,
            telemetry=telemetry)

