"""Roofline-term extraction from a compiled dry-run artifact.

compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
memory term     = HLO_bytes / (chips × HBM_bw)
collective term = wire_bytes_per_chip / link_bw

FLOPs/bytes come from the loop-aware HLO walker (:mod:`.hlo_cost`);
collective bytes are parsed from the post-SPMD HLO text (shapes there are
per-device), with ring wire formulas per op:
  all-reduce      2(g−1)/g × result
  all-gather      (g−1)/g × result
  reduce-scatter  (g−1)   × result        (operand = g × result)
  all-to-all      (g−1)/g × result
  collective-permute       result

Hardware constants are an :class:`HW` dataclass, not module globals: the
autotuner ranks candidate configurations by these terms, so scoring a CPU
container against TPU v5e numbers would rank against the wrong machine.
:func:`detect_hw` looks up ``jax.devices()[0].device_kind`` in
:data:`HW_PRESETS`; a device kind that is not in the table is an error,
never a default.  The ``REPRO_HW`` env var forces a preset by key or
name, and ``REPRO_HW_PEAK_FLOPS`` / ``REPRO_HW_HBM_BW`` /
``REPRO_HW_LINK_BW`` (plus ``REPRO_HW_CACHE_BW`` / ``REPRO_HW_CACHE_BYTES``
for the cache-aware memory term) override individual terms (calibrating
against a measured machine).
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional

from .dtype_bytes import DTYPE_BYTES as _DTYPE_BYTES

__all__ = ["HW", "HW_PRESETS", "detect_hw", "collective_bytes", "roofline",
           "Roofline"]


@dataclasses.dataclass(frozen=True)
class HW:
    """Peak rates the three roofline terms divide by (per chip).

    ``cache_bw`` / ``cache_bytes`` turn on the cache-aware memory term
    (Ilic et al.'s cache-aware roofline): when the executable's static
    working set (``temp_size_in_bytes``) fits the last-level cache the
    memory term divides by ``cache_bw``; past it, the effective bandwidth
    blends toward ``hbm_bw`` in proportion to the spilled fraction.  Both
    ``None`` (the default) keeps the classic flat-``hbm_bw`` model.
    """

    name: str
    peak_flops: float   # FLOP/s
    hbm_bw: float       # bytes/s to HBM (or host RAM on CPU)
    link_bw: float      # bytes/s per inter-chip link
    cache_bw: Optional[float] = None     # bytes/s from last-level cache
    cache_bytes: Optional[float] = None  # last-level cache capacity


#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Only the cpu
#: preset models the cache hierarchy (~30 MB LLC at ~8× DRAM bandwidth): on
#: CPU the candidates' total flops/bytes are nearly flat and *locality* —
#: whether the λ-chunk × packed-factor working set stays cache-resident —
#: is what separates their wall time; the accelerator presets keep the
#: classic HBM-only term (VMEM-sized tiles are the kernels' own contract).
HW_PRESETS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM, 1,600 Gbit/s of interchip interconnect over 4 links
    "TPU v5 lite": HW(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                      link_bw=50e9),
    # a deliberately rough server-class host: on CPU the tuner only needs
    # the *relative* ordering of candidates, which all share the platform
    "cpu": HW(name="cpu", peak_flops=1e11, hbm_bw=5e10, link_bw=2.5e10,
              cache_bw=4e11, cache_bytes=3e7),
}


def detect_hw() -> HW:
    """The :class:`HW` for this process: the ``REPRO_HW`` preset (by key or
    name) if set, else the preset for this process's device kind, with
    per-term ``REPRO_HW_*`` numeric overrides applied on top.  Raises for a
    device kind the table does not hold."""
    name = os.environ.get("REPRO_HW", "").strip()
    if name:
        by_name = {hw.name: hw for hw in HW_PRESETS.values()}
        hw = HW_PRESETS.get(name) or by_name.get(name.lower())
        if hw is None:
            raise ValueError(f"REPRO_HW={name!r}: no such preset; "
                             f"have {sorted(by_name)}")
    else:
        import jax
        kind = jax.devices()[0].device_kind
        if kind not in HW_PRESETS:
            raise ValueError(f"no peak rates for device kind {kind!r}; "
                             f"have {sorted(HW_PRESETS)} (or set REPRO_HW)")
        hw = HW_PRESETS[kind]
    overrides = {}
    for field, env in (("peak_flops", "REPRO_HW_PEAK_FLOPS"),
                       ("hbm_bw", "REPRO_HW_HBM_BW"),
                       ("link_bw", "REPRO_HW_LINK_BW"),
                       ("cache_bw", "REPRO_HW_CACHE_BW"),
                       ("cache_bytes", "REPRO_HW_CACHE_BYTES")):
        val = os.environ.get(env)
        if val:
            overrides[field] = float(val)
    if overrides:
        hw = dataclasses.replace(hw, name=hw.name + "+env", **overrides)
    return hw


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^=]*?\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota format [n_groups,group_size]<=[total]
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].lstrip("{")
        ids = [x for x in first.split(",") if x.strip()]
        return max(len(ids), 1)
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind (ring formulas)."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if "-done" in line:
            continue  # async pair: count the -start only
        result_type, op = m.group(1), m.group(2)
        size = _shape_bytes(result_type)
        g = _group_size(line)
        if g <= 1:
            continue
        if op == "all-reduce":
            wire = 2 * (g - 1) / g * size
        elif op == "all-gather":
            wire = (g - 1) / g * size
        elif op == "reduce-scatter":
            wire = (g - 1) * size
        elif op == "all-to-all":
            wire = (g - 1) / g * size
        else:  # collective-permute
            wire = size
        out[op] = out.get(op, 0.0) + wire
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    wire_bytes: float            # per-device collective wire bytes
    by_collective: Dict[str, float]
    chips: int
    hw: Optional[HW] = None      # None = detect for this process
    temp_bytes: Optional[float] = None  # static working set (temp buffers)

    def __post_init__(self):
        if self.hw is None:
            self.hw = detect_hw()

    @property
    def compute_s(self) -> float:
        return self.flops / self.hw.peak_flops

    @property
    def effective_bw(self) -> float:
        """Bandwidth the memory term divides by: ``hbm_bw`` flat unless the
        HW models a cache AND the executable's working set is known — then
        cache-resident working sets stream at ``cache_bw`` and spilled ones
        blend toward ``hbm_bw`` by the spilled fraction."""
        hw = self.hw
        if (hw.cache_bw is None or hw.cache_bytes is None
                or not self.temp_bytes):
            return hw.hbm_bw
        if self.temp_bytes <= hw.cache_bytes:
            return hw.cache_bw
        resident = hw.cache_bytes / self.temp_bytes
        return resident * hw.cache_bw + (1.0 - resident) * hw.hbm_bw

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.effective_bw

    @property
    def collective_s(self) -> float:
        return self.wire_bytes / self.hw.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def summary(self) -> Dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "wire_bytes_per_device": self.wire_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "step_s": self.step_s,
            "bottleneck": self.bottleneck,
            "by_collective": self.by_collective,
            "hw": self.hw.name,
            "temp_bytes_per_device": self.temp_bytes,
            "effective_bw": self.effective_bw,
        }


def roofline(compiled, chips: int, hw: Optional[HW] = None) -> Roofline:
    """Three roofline terms from the compiled artifact.

    Uses the loop-aware HLO walker (hlo_cost) rather than
    ``compiled.cost_analysis()`` because the latter counts while-loop
    (lax.scan layer stack / lax.map λ-chunk stream) bodies exactly once —
    see EXPERIMENTS.md §Roofline for the calibration.  All values are
    per-device; ``hw=None`` detects the platform preset.
    """
    from . import hlo_cost

    text = compiled.as_text()
    cost = hlo_cost.analyze_hlo(text)
    temp = None
    try:
        temp = float(compiled.memory_analysis().temp_size_in_bytes)
    except Exception:  # noqa: BLE001 — backends without memory_analysis
        pass
    return Roofline(flops=cost.flops, hbm_bytes=cost.hbm_bytes,
                    wire_bytes=cost.wire_bytes, by_collective=dict(cost.wire),
                    chips=chips, hw=hw, temp_bytes=temp)
