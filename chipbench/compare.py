"""How ``correct`` is decided: every schedule of the window against the
plain reference (:mod:`chipbench.reference`), which imports nothing of
the compiler under test.

Four numbers are compared, each with the limit its configuration file
gives (``limits``):

- ``violations``: guarantees the configuration states, broken by any
  schedule of the window, re-derived by the reference (rail count,
  voltage menu and rail set, gating legality, layer and bank timeline,
  the period, the deadline within the compiler's stated slop, a
  schedule for every request).  Exact: limit 0.
- ``ledger_rel_err``: the widest relative gap between a schedule's
  recorded ledger (``t_infer``; ``e_total``, ``e_op``, ``e_trans``,
  ``e_idle`` at the scale of ``e_total``) and the reference's
  re-derivation of the same voltages.  Beside it the check reads the
  ledger's precision control: the same gap for the reference's ledger
  summed in float32 in the place of the program's
  (``ledger_f32_rel_err``).
- ``energy_gap``: the widest relative gap by which the re-derived
  energy of a sampled schedule lies above the reference's lower bound
  on the optimum of its request (:mod:`chipbench.reference.optimum`).
  The bound is a Lagrangian dual, so a schedule at the optimum reads
  the bound's own duality gap, not 0.
- ``float_bits_short``: how many bits the narrowest floating-point
  device array the program held after the window falls short of the
  precision the configuration states (0 where it held none).  Exact:
  limit 0.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.reference import optimum, physics

V_GATED = physics.V_GATED


def _is_schedule(value) -> bool:
    return hasattr(value, "layer_voltages") and hasattr(value, "rails")


def violations(sched, req, config: dict, acc: physics.Accelerator,
               ref: dict) -> list[str]:
    """Guarantees of the configuration that ``sched`` breaks, judged on
    the reference's re-derivation ``ref`` of its voltages."""
    if not _is_schedule(sched):
        return [f"no schedule for a feasible request: {sched!r}"]
    layers = req.layers()
    out = []
    if not sched.feasible:
        out.append("schedule marked infeasible")
    if sched.t_max != 1.0 / req.rate_hz:
        out.append(f"period {sched.t_max!r} is not 1/rate "
                   f"{1.0 / req.rate_hz!r}")
    if len(sched.layer_voltages) != len(layers):
        return out + [f"{len(sched.layer_voltages)} layer rows for "
                      f"{len(layers)} layers"]
    levels, rails = set(acc.levels()), set(sched.rails)
    if len(rails) > config["guarantees"]["max_rails"]:
        out.append(f"{len(rails)} rails")
    if not rails <= levels:
        out.append(f"rails {sorted(rails - levels)} off the menu")
    for i, (volts, layer) in enumerate(zip(sched.layer_voltages, layers)):
        for d, v in enumerate(volts):
            if v == V_GATED:
                if d != physics.D_RRAM or layer.weight_bytes:
                    out.append(f"layer {i} domain {d} gated illegally")
            elif v not in rails:
                out.append(f"layer {i} domain {d} at {v} V, not a rail")
    banks = physics.Banks.place(layers, acc)
    if list(sched.awake_banks) != [banks.awake(i, True)
                                   for i in range(len(layers))]:
        out.append("awake banks contradict the bank plan")
    if sched.n_rail_switches != ref["n_rail_switches"]:
        out.append(f"{sched.n_rail_switches} rail switches recorded, "
                   f"{ref['n_rail_switches']} in the voltages")
    slop = config["guarantees"]["deadline_slop_s"]
    if ref["t_infer"] > sched.t_max + slop:
        out.append(f"deadline missed: {ref['t_infer']!r} s > "
                   f"{sched.t_max!r} s")
    return out


def ledger_rel_err(sched, ref: dict) -> float:
    """Widest relative gap between the recorded ledger and ``ref``."""
    scale = abs(ref["e_total"])
    errs = [abs(sched.t_infer - ref["t_infer"]) / ref["t_infer"]]
    errs += [abs(getattr(sched, f) - ref[f]) / scale
             for f in ("e_total", "e_op", "e_trans", "e_idle")]
    return max(errs)


class _Ledger:
    """A re-derived ledger, read like a schedule's."""

    def __init__(self, fields: dict):
        self.__dict__.update(fields)


def reference_ledger(sched, req, acc, dtype=float) -> dict:
    return physics.ledger(req.layers(), acc, sched.layer_voltages,
                          sched.t_max, gating=True, dtype=dtype)


def sample(records, k: int, seed: int) -> list[int]:
    """Indices of the records held against the bound on the optimum:
    ``k`` drawn from the seed, always with the slowest request among
    them."""
    n = len(records)
    if n <= k:
        return list(range(n))
    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 64),
                                                        7]))
    slowest = max(range(n), key=lambda i: records[i].latency)
    rest = [i for i in range(n) if i != slowest]
    picked = rng.choice(len(rest), size=k - 1, replace=False)
    return sorted([slowest] + [rest[j] for j in picked])


def float_bits_short(config: dict, dtypes) -> int:
    """Bits by which the narrowest floating ``dtypes`` (numpy dtypes of
    the program's device arrays) fall short of the configuration's
    ``precision``."""
    want = np.dtype(config["precision"]).itemsize * 8
    bits = [np.dtype(d).itemsize * 8 for d in dtypes
            if np.issubdtype(np.dtype(d), np.floating)]
    return max(0, want - min(bits)) if bits else 0


def check(records, config: dict, seed: int, device_dtypes=()) -> dict:
    """Compare the window's results; returns the numbers, their limits,
    and the first few faults found.  ``device_dtypes`` are the dtypes of
    the device arrays the program held after the window."""
    acc = physics.Accelerator(**config.get("accelerator", {}))
    faults: list[str] = []
    n_viol, worst, control = 0, 0.0, 0.0
    derived = {}
    for rec in records:
        if rec.error is not None:
            faults.append(f"request {rec.request.index}: {rec.error}")
            n_viol += 1
            continue
        ref = reference_ledger(rec.result, rec.request, acc) \
            if _is_schedule(rec.result) else None
        bad = violations(rec.result, rec.request, config, acc, ref or {})
        n_viol += len(bad)
        faults += [f"request {rec.request.index} "
                   f"{rec.request.label}: {b}" for b in bad]
        if ref is not None:
            derived[id(rec)] = ref
            worst = max(worst, ledger_rel_err(rec.result, ref))
            f32 = reference_ledger(rec.result, rec.request, acc,
                                   dtype=np.float32)
            control = max(control, ledger_rel_err(_Ledger(f32), ref))
    scheduled = [r for r in records if id(r) in derived]
    gap = None                  # no schedule: nothing can be held to it
    for i in sample(scheduled, config["reference_sample"], seed):
        rec = scheduled[i]
        req, energy = rec.request, derived[id(rec)]["e_total"]
        bound = optimum.lower_bound(req.network, req.input_hw, req.rate_hz,
                                    config["n_max_rails"], acc,
                                    cutoff=energy)
        gap = max(gap if gap is not None else -math.inf,
                  (energy - bound) / bound)
    limits = config["limits"]
    numbers = {
        "violations": {"value": n_viol, "limit": limits["violations"]},
        "ledger_rel_err": {"value": worst,
                           "limit": limits["ledger_rel_err"]},
        "energy_gap": {"value": gap, "limit": limits["energy_gap"]},
        "float_bits_short": {"value": float_bits_short(config,
                                                       device_dtypes),
                             "limit": limits["float_bits_short"]},
    }
    return {"numbers": numbers, "faults": faults[:20],
            "ledger_f32_rel_err": control,
            "correct": bool(records) and all(
                v["value"] is not None and v["value"] <= v["limit"]
                for v in numbers.values())}
