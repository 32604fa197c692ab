"""The plain reference of the 40 nm edge accelerator's physics.

A self-contained statement of what a power schedule's voltages cost on
the accelerator of arXiv 2603.23882 (§3-§5): per-layer cycles and
event energies from the layer's shape, alpha-power DVFS, C·V² switch
energy, sequential RRAM bank placement with ping-pong prefetch, and the
terminal idle/deep-sleep interval.  It imports nothing of the compiler
under test and is written as scalar loops, in the operation order of
the paper's formulas, so that a schedule's recorded ledger can be held
against it.

Used by :mod:`chipbench.compare` to re-derive every schedule the window
produced and to check the guarantees its configuration states.
"""

from __future__ import annotations

import dataclasses
import math

V_GATED = 0.0
D_COMPUTE, D_FEEDER, D_RRAM = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class Accelerator:
    """The accelerator's published constants (paper Fig. 4, §5.2)."""

    pe_rows: int = 8
    pe_cols: int = 8
    v_min: float = 0.9
    v_max: float = 1.3
    v_step: float = 0.05
    v_nom: float = 1.1
    f_compute_max: float = 500e6
    f_feeder_max: float = 500e6
    f_rram_max: float = 100e6
    e_mac: float = 0.25e-12
    e_sram_lane: float = 1.2e-12
    e_sram_weight: float = 1.8e-12
    e_rram_read: float = 12.0e-12
    e_feeder_byte: float = 1.5e-12
    leak_compute: float = 0.60e-3
    leak_feeder: float = 0.20e-3
    leak_rram_bank: float = 0.12e-3
    rram_bank_bytes: int = 64 * 1024
    idle_residual_dyn: float = 0.15
    sleep_retention_frac: float = 0.03
    sleep_wake_energy: float = 25e-9
    sleep_wake_latency: float = 2e-6
    t_rail: float = 15e-9
    t_wake: float = 5e-9
    e_switch_nom: float = 1e-9
    v_th: float = 0.35
    alpha: float = 1.35
    leak_beta: float = 2.2

    def levels(self) -> tuple[float, ...]:
        n = int(round((self.v_max - self.v_min) / self.v_step)) + 1
        return tuple(round(self.v_min + i * self.v_step, 4)
                     for i in range(n))

    def _shape(self, v: float) -> float:
        return (v - self.v_th) ** self.alpha / v

    def f_nom(self, domain: int) -> float:
        f_max = (self.f_compute_max, self.f_feeder_max,
                 self.f_rram_max)[domain]
        return f_max * self._shape(self.v_nom) / self._shape(self.v_max)

    def freq(self, domain: int, v: float) -> float:
        if v <= self.v_th:
            return 0.0
        scale = ((v - self.v_th) ** self.alpha / v) / (
            (self.v_nom - self.v_th) ** self.alpha / self.v_nom)
        return self.f_nom(domain) * scale

    def dyn_scale(self, v: float) -> float:
        return (v / self.v_nom) ** 2

    def leak(self, leak_nom: float, v: float) -> float:
        if v <= V_GATED:
            return 0.0
        return leak_nom * (v / self.v_nom) * math.exp(
            self.leak_beta * (v - self.v_nom))

    # -- transitions (§5.2) --------------------------------------------
    def switch_latency(self, a: float, b: float) -> float:
        if a == b:
            return 0.0
        if a == V_GATED:
            return self.t_wake
        if b == V_GATED:
            return 0.0
        return self.t_rail

    def switch_energy(self, a: float, b: float) -> float:
        if a == b:
            return 0.0
        swing = self.v_max ** 2 - self.v_min ** 2
        c = self.e_switch_nom / swing if swing > 0 else 0.0
        hi, lo = max(a, b), min(a, b)
        if lo == V_GATED:
            return c * hi ** 2
        return c * (hi ** 2 - lo ** 2)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def characterize(spec, acc: Accelerator):
    """(cycles per domain, dynamic energy per domain at v_nom) of one
    layer; ``spec`` is a :class:`chipbench.reference.networks.Layer`."""
    rows = acc.pe_rows * acc.pe_cols
    if spec.kind == "conv":
        cyc_c = (_ceil_div(spec.p_out, acc.pe_rows)
                 * _ceil_div(spec.c_out, acc.pe_cols)
                 * spec.c_in * spec.kernel * spec.kernel)
    elif spec.kind == "dwconv":
        cyc_c = (_ceil_div(spec.p_out, acc.pe_rows)
                 * _ceil_div(spec.c_out, acc.pe_cols)
                 * spec.kernel * spec.kernel)
    elif spec.kind == "fc":
        cyc_c = (_ceil_div(spec.c_out, acc.pe_cols)
                 * _ceil_div(spec.c_in, acc.pe_rows) * acc.pe_rows)
    elif spec.kind == "attn":
        cyc_c = int(spec.macs / rows * 1.15) + 1
    else:
        cyc_c = _ceil_div(spec.p_out * spec.c_out, rows)
    moved = spec.act_in_bytes + spec.act_out_bytes + spec.weight_bytes
    lane_bytes = spec.macs / 8 + spec.act_in_bytes + spec.act_out_bytes
    wbuf_bytes = spec.macs / 8
    e_c = (spec.macs * acc.e_mac + lane_bytes * acc.e_sram_lane
           + wbuf_bytes * acc.e_sram_weight)
    cycles = (int(cyc_c), int(_ceil_div(moved, 8)),
              int(_ceil_div(spec.weight_bytes, 8)))
    dyn = (float(e_c), float(moved * acc.e_feeder_byte),
           float(spec.weight_bytes * acc.e_rram_read))
    return cycles, dyn


@dataclasses.dataclass(frozen=True)
class Banks:
    """Sequential weight placement over fixed-size RRAM banks."""

    n_banks: int
    spans: tuple[tuple[int, int], ...]

    @classmethod
    def place(cls, layers, acc: Accelerator) -> "Banks":
        spans, offset = [], 0
        for spec in layers:
            wb = spec.weight_bytes
            if wb == 0:
                spans.append((-1, -1))
                continue
            spans.append((offset // acc.rram_bank_bytes,
                          (offset + wb - 1) // acc.rram_bank_bytes))
            offset += wb
        return cls(max(1, -(-offset // acc.rram_bank_bytes)), tuple(spans))

    def _live(self, layers_at) -> set[int]:
        live: set[int] = set()
        for li in layers_at:
            if 0 <= li < len(self.spans) and self.spans[li][0] >= 0:
                lo, hi = self.spans[li]
                live.update(range(lo, hi + 1))
        return live

    def awake(self, i: int, gating: bool) -> int:
        if not gating:
            return self.n_banks
        return max(len(self._live((i, i + 1))), 1)

    def wakes(self, i: int, gating: bool) -> int:
        if not gating or i + 1 >= len(self.spans) \
                or self.spans[i + 1][0] < 0:
            return 0
        lo, hi = self.spans[i + 1]
        return len(set(range(lo, hi + 1)) - self._live((i - 1, i)))


def layer_op(cycles, dyn, i: int, acc: Accelerator, banks: Banks,
             volts, gating: bool) -> tuple[float, float]:
    """T_op and E_op of layer ``i`` at one voltage per domain."""
    v_c, v_f, v_r = volts
    n_awake = banks.awake(i, gating)
    wakes = banks.wakes(i, gating)
    t_c = cycles[0] / acc.freq(D_COMPUTE, v_c)
    e_c = dyn[0] * acc.dyn_scale(v_c)
    l_c = acc.leak(acc.leak_compute, v_c)
    t_f = cycles[1] / acc.freq(D_FEEDER, v_f)
    e_f = dyn[1] * acc.dyn_scale(v_f)
    l_f = acc.leak(acc.leak_feeder, v_f)
    if v_r == V_GATED:
        t_r = e_r = l_r = e_wake = 0.0
    else:
        t_r = cycles[2] / acc.freq(D_RRAM, v_r)
        e_r = dyn[2] * acc.dyn_scale(v_r)
        l_r = n_awake * acc.leak(acc.leak_rram_bank, v_r)
        e_wake = wakes * (acc.switch_energy(V_GATED, v_r) / banks.n_banks)
    t_op = max(max(t_c, t_f), t_r) + wakes * acc.t_wake
    e_op = ((e_c + e_f) + e_r) + ((l_c + l_f) + l_r) * t_op + e_wake
    return t_op, e_op


def idle(acc: Accelerator, n_banks: int, slack: float,
         gating: bool) -> tuple[float, int]:
    """Energy of the terminal idle interval and whether the chip stays
    active (1) or deep-sleeps (0) through it (§4.2)."""
    if gating:
        p_idle = (acc.leak_compute + acc.leak_feeder
                  + acc.leak_rram_bank) * (1.0 + acc.idle_residual_dyn)
    else:
        p_idle = (acc.leak_compute + acc.leak_feeder
                  + acc.leak_rram_bank * n_banks) \
            * (1.0 + acc.idle_residual_dyn)
    p_sleep = (acc.leak_compute + acc.leak_feeder
               + acc.leak_rram_bank * n_banks) * acc.sleep_retention_frac
    if slack <= 0:
        return 0.0, 1
    active = p_idle * slack
    if not gating or slack <= acc.sleep_wake_latency:
        return active, 1
    sleep = acc.sleep_wake_energy + p_sleep * slack
    return min(active, sleep), int(active < sleep)


def ledger(layers, acc: Accelerator, layer_voltages, t_max: float, *,
           gating: bool = True, dtype=float) -> dict:
    """Re-derive the ledger of a schedule that runs ``layers`` at
    ``layer_voltages`` (one tuple of domain voltages per layer) under a
    period ``t_max``.  ``dtype`` is the float type the totals are
    accumulated in (the precision control passes ``numpy.float32``)."""
    banks = Banks.place(layers, acc)
    t_sum, e_op, e_tr = dtype(0.0), dtype(0.0), dtype(0.0)
    switches = 0
    for i, spec in enumerate(layers):
        cycles, dyn = characterize(spec, acc)
        t, e = layer_op(cycles, dyn, i, acc, banks, layer_voltages[i],
                        gating)
        t_sum += dtype(t)
        e_op += dtype(e)
    for i in range(len(layers) - 1):
        va, vb = layer_voltages[i], layer_voltages[i + 1]
        t_b = 0.0
        for a, b in zip(va, vb):
            t_b = max(t_b, acc.switch_latency(a, b))
            e_tr += dtype(acc.switch_energy(a, b))
        t_sum += dtype(t_b)
        switches += any(a != b and a != V_GATED and b != V_GATED
                        for a, b in zip(va, vb))
    t_infer = float(t_sum)
    e_idle, z = idle(acc, banks.n_banks, t_max - t_infer, gating)
    e_total = float(dtype(e_op) + dtype(e_tr) + dtype(e_idle))
    return {"t_infer": t_infer, "e_op": float(e_op),
            "e_trans": float(e_tr), "e_idle": float(e_idle),
            "e_total": e_total, "z_active_idle": z,
            "n_rail_switches": switches, "n_banks": banks.n_banks}


def max_rate(layers, acc: Accelerator) -> float:
    """Highest inference rate any schedule can meet: 1 / the latency
    with every domain at ``v_max``."""
    t = 0.0
    for spec in layers:
        cycles, _ = characterize(spec, acc)
        t += max(c / acc.freq(d, acc.v_max) for d, c in enumerate(cycles))
    return 1.0 / t
