"""Alpha-beta collective cost formulas over ICI/DCN link tables.

The planner (``paddle.planner``) scores every candidate mesh analytically:
each collective a parallelism axis implies is priced with the classic
ring-algorithm alpha-beta model

    time = latency_term * alpha  +  traffic_term / bandwidth

where ``alpha`` is the per-hop launch latency of the link the axis rides
(ICI inside a slice, DCN across slices) and the traffic term is the bytes
each participant must move on the bottleneck link. The formulas (``n`` =
group size, ``B`` = payload bytes per participant):

==============  ======================  =====================
collective      traffic term            latency term
==============  ======================  =====================
all-reduce      ``2*(n-1)/n * B``       ``2*(n-1)``
all-gather      ``(n-1)/n * B``         ``n-1``
reduce-scatter  ``(n-1)/n * B``         ``n-1``
all-to-all      ``(n-1)/n * B``         ``n-1``
p2p (send)      ``B``                   ``1``
==============  ======================  =====================

(all-reduce = reduce-scatter + all-gather, hence the doubled terms; for
all-to-all each rank keeps 1/n of its shard and exchanges the rest.)

These are upper-bound *ordering* costs, not measurements: they answer
"which candidate's communication is cheapest on this topology", the
question the planner's search needs — and they are unit-tested against
hand-computed values (tests/test_planner.py) so the formulas cannot drift
silently. ``CHIP_PRESETS`` carries public per-chip numbers (per-direction
aggregate ICI/DCN bandwidth per chip, HBM capacity, peak dense FLOPs);
the ``cpu`` preset exists so the 8-device test mesh plans deterministically.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["LinkSpec", "ChipSpec", "CHIP_PRESETS", "chip_preset",
           "chip_name", "chip_vmem_bytes", "all_reduce_s", "all_gather_s",
           "reduce_scatter_s", "all_to_all_s", "p2p_s",
           "collective_s", "COLLECTIVE_FORMULAS"]


@dataclass(frozen=True)
class LinkSpec:
    """One interconnect tier: per-chip aggregate bandwidth + hop latency."""
    bandwidth_gbps: float   # bytes/s * 1e-9, per direction, per chip
    latency_us: float       # alpha: per-hop launch latency

    @property
    def bytes_per_s(self) -> float:
        return self.bandwidth_gbps * 1e9

    @property
    def latency_s(self) -> float:
        return self.latency_us * 1e-6

    def to_dict(self) -> dict:
        return {"bandwidth_gbps": self.bandwidth_gbps,
                "latency_us": self.latency_us}


class ChipSpec(dict):
    """A chip preset: a plain dict (the planner indexes ``preset["ici"]``)
    that also answers attribute access (``chip_preset("v5e").vmem_bytes``)
    so the kernels and the kernel analyzer read one source of truth."""

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name) from None


#: Public per-chip numbers (TPU system datasheets). ``ici`` is the
#: per-chip aggregate inter-chip-interconnect bandwidth inside a slice;
#: ``dcn`` the per-chip share of the data-center network between slices.
#: ``peak_flops`` is dense bf16. ``hbm_gbps`` is the per-chip HBM
#: bandwidth — the memory side of the per-kernel roofline
#: (:func:`roofline_ms`). ``vmem_bytes`` is the per-core
#: VMEM the Pallas pipeline stages blocks through (~16 MiB/core on
#: current chips; v6e doubles it) — the budget every kernel's block
#: picker and the PK200 residency check share.
_MIB = 1024 * 1024
CHIP_PRESETS = {
    "v4":  ChipSpec(ici=LinkSpec(300.0, 1.0), dcn=LinkSpec(25.0, 10.0),
                    hbm_gb=32.0, hbm_gbps=1200.0, peak_flops=275e12,
                    vmem_bytes=16 * _MIB),
    "v5e": ChipSpec(ici=LinkSpec(186.0, 1.0), dcn=LinkSpec(25.0, 10.0),
                    hbm_gb=16.0, hbm_gbps=820.0, peak_flops=197e12,
                    vmem_bytes=16 * _MIB),
    "v5p": ChipSpec(ici=LinkSpec(600.0, 1.0), dcn=LinkSpec(25.0, 10.0),
                    hbm_gb=95.0, hbm_gbps=2765.0, peak_flops=459e12,
                    vmem_bytes=16 * _MIB),
    "v6e": ChipSpec(ici=LinkSpec(448.0, 1.0), dcn=LinkSpec(25.0, 10.0),
                    hbm_gb=32.0, hbm_gbps=1640.0, peak_flops=918e12,
                    vmem_bytes=32 * _MIB),
    # the virtual 8-device CPU test mesh: numbers chosen so plans are
    # deterministic and memory is never the binding constraint by accident;
    # vmem_bytes mirrors v5e so interpret-mode kernels pick real shapes
    "cpu": ChipSpec(ici=LinkSpec(10.0, 1.0), dcn=LinkSpec(1.0, 50.0),
                    hbm_gb=4.0, hbm_gbps=50.0, peak_flops=5e10,
                    vmem_bytes=16 * _MIB),
}


def chip_preset(name: str) -> ChipSpec:
    try:
        return CHIP_PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown chip preset {name!r} "
                       f"(have {sorted(CHIP_PRESETS)})") from None


#: device_kind substrings (lower-case, first match wins) -> preset name
_KIND_TO_PRESET = (("v6 lite", "v6e"), ("v6e", "v6e"), ("v5 lite", "v5e"),
                   ("v5lite", "v5e"), ("v5e", "v5e"), ("v5p", "v5p"),
                   ("v5", "v5p"), ("v4", "v4"))


def chip_name(name: str | None = None) -> str:
    """The preset to cost against: an explicit ``name``, else
    ``$PADDLE_TPU_CHIP``, else the attached device's kind. An unknown
    name or kind raises — there is no default chip."""
    import os
    name = name or os.environ.get("PADDLE_TPU_CHIP")
    if name:
        chip_preset(name)
        return name
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return "cpu"
    kind = dev.device_kind.lower()
    for marker, preset in _KIND_TO_PRESET:
        if marker in kind:
            return preset
    raise KeyError(f"no chip preset for device kind {dev.device_kind!r} "
                   f"(have {sorted(CHIP_PRESETS)})")


def chip_vmem_bytes(name: str | None = None) -> int:
    """Per-core VMEM budget of :func:`chip_name`'s preset."""
    return int(chip_preset(chip_name(name))["vmem_bytes"])


def roofline_ms(flops: float, hbm_bytes: float,
                name: str | None = None) -> float:
    """Analytic per-kernel time: the max of the compute and HBM legs of
    the chip's roofline, in milliseconds. The prediction the tuning
    cache's measured entries are compared against (``kernel_cost``'s
    ``predicted_vs_measured``)."""
    chip = chip_preset(chip_name(name))
    compute_s = float(flops) / float(chip["peak_flops"])
    memory_s = float(hbm_bytes) / (float(chip["hbm_gbps"]) * 1e9)
    return max(compute_s, memory_s) * 1e3


def all_reduce_s(nbytes: float, n: int, link: LinkSpec) -> float:
    """Ring all-reduce: 2*(n-1)/n of the payload over the link + 2*(n-1)
    hops of latency. 0 for a single-member group."""
    if n <= 1:
        return 0.0
    return (2.0 * (n - 1) / n) * nbytes / link.bytes_per_s \
        + 2.0 * (n - 1) * link.latency_s


def all_gather_s(nbytes: float, n: int, link: LinkSpec) -> float:
    """Ring all-gather of a ``nbytes`` result: each rank receives the
    (n-1)/n of the full value it does not already hold."""
    if n <= 1:
        return 0.0
    return ((n - 1) / n) * nbytes / link.bytes_per_s \
        + (n - 1) * link.latency_s


def reduce_scatter_s(nbytes: float, n: int, link: LinkSpec) -> float:
    """Ring reduce-scatter of a ``nbytes`` input: the all-gather mirror."""
    return all_gather_s(nbytes, n, link)


def all_to_all_s(nbytes: float, n: int, link: LinkSpec) -> float:
    """Each rank re-shards a ``nbytes`` local shard: keeps 1/n, sends the
    remaining (n-1)/n (one message per peer)."""
    if n <= 1:
        return 0.0
    return ((n - 1) / n) * nbytes / link.bytes_per_s \
        + (n - 1) * link.latency_s


def p2p_s(nbytes: float, link: LinkSpec) -> float:
    """One point-to-point transfer (pipeline boundary send)."""
    return nbytes / link.bytes_per_s + link.latency_s


COLLECTIVE_FORMULAS = {
    "all-reduce": all_reduce_s,
    "all-gather": all_gather_s,
    "reduce-scatter": reduce_scatter_s,
    "all-to-all": all_to_all_s,
}


def collective_s(op: str, nbytes: float, n: int, link: LinkSpec) -> float:
    """Dispatch by op name ("all-reduce" | "all-gather" | "reduce-scatter"
    | "all-to-all" | "p2p")."""
    if op == "p2p":
        return p2p_s(nbytes, link)
    try:
        return COLLECTIVE_FORMULAS[op](nbytes, n, link)
    except KeyError:
        raise ValueError(f"unknown collective {op!r} "
                         f"(have {sorted(COLLECTIVE_FORMULAS)} + p2p)") \
            from None
