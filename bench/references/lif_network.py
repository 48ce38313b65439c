"""Plain reference of the connectome LIF network the configurations state.

A straightforward re-statement of the model, written from its equations and
independent of the simulator: a numpy loop over time steps that delivers
every spike of step t to every target of its source at step t + D (one
uniform synaptic delay), with no budgets, no compaction and no
partitioning, and integrates the two-state current-based LIF (paper Eq. 1,
forward Euler) in the configuration's precision:

* ``fixed_point``: Q19.12 integers in units of ``w_scale`` (the Loihi 2
  microcode arithmetic: small coefficients held at 16 fractional bits and
  applied as ``((x >> 2) * c16) >> 14``);
* float32 otherwise.

The stimulus is drawn with ``jax.random`` exactly as the model's RNG
contract states, so the reference sees the same Poisson and background
events as the system under test:

* every step splits its key into ``1 + max(2, parts)`` keys, keeps the
  first as the next step's key and hands key ``1 + j`` to stochastic
  stimulus part ``j`` (in the traffic's order);
* a Poisson part on a chosen population draws one Bernoulli(rate*dt) per
  chosen neuron (``default_rng(seed).choice(n, k, replace=False)`` picks
  them); Loihi drive adds ``weight`` units to ``g``, Brian2 drive adds
  ``1.5 * v_th`` mV to ``v``; background draws one per neuron and forces a
  spike;
* on a partitioned layout every partition owns a contiguous near-equal
  neuron range, padded to ``pad_multiple`` neurons, with its own key
  (``split(PRNGKey(seed), parts)[p]``), and every part draws over the
  partition's padded range (pads never spike).

``frac_bits`` / ``float_dtype`` select the arithmetic; the default is the
precision the configuration states, and the benchmark's control computes
the same run one step lower (8 fractional bits, or bfloat16).
"""

from __future__ import annotations

import functools

import jax
import ml_dtypes
import numpy as np

from bench.compare import Answer

BFLOAT16 = np.dtype(ml_dtypes.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draws(key, steps: int, n_split: int, slot: int, shape_prob: tuple):
    shape, prob = shape_prob

    def body(k, _):
        ks = jax.random.split(k, n_split)
        return ks[0], jax.random.bernoulli(ks[slot], prob, shape)

    return jax.lax.scan(body, key, None, length=steps)[1]


def pick(n: int, k: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).choice(n, size=min(int(k), n),
                                              replace=False)


def stimulus_parts(traffic: dict) -> list[dict]:
    """The traffic's stochastic stimulus parts, in key order."""
    sc, prm = traffic["scenario"], traffic["params"]
    if sc == "sugar_feeding":
        parts = [{"kind": "poisson", "n": prm["n_sugar"],
                  "rate_hz": prm["rate_hz"]}]
        if prm.get("background_hz", 0.0) > 0:
            parts.append({"kind": "background",
                          "rate_hz": prm["background_hz"]})
        return parts
    if sc == "activity_sweep":
        hz = prm["background_hz"]
        return [{"kind": "background", "rate_hz": hz}] if hz > 0 else []
    raise ValueError(f"the reference has no scenario {sc!r}")


def _layout(n: int, parts: int, pad: int):
    sizes = np.full(parts, n // parts, np.int64)
    sizes[: n % parts] += 1
    u = int(-(-sizes.max() // pad) * pad)
    return np.concatenate([[0], np.cumsum(sizes)]), u


def drive_events(n: int, traffic: dict, steps: int, lane_seed: int,
                 stim_seed: int, layout: dict | None):
    """Per stimulus part: ``(part, draws [T, n] bool)`` in original ids."""
    parts = stimulus_parts(traffic)
    n_split = 1 + max(2, len(parts))
    out = []
    for j, part in enumerate(parts):
        prob = part["rate_hz"] * traffic["dt_ms"] * 1e-3
        full = np.zeros((steps, n), bool)
        idx = (pick(n, part["n"], stim_seed) if part["kind"] == "poisson"
               else None)
        if layout is None:
            shape = (len(idx),) if idx is not None else (n,)
            d = np.asarray(_draws(jax.random.PRNGKey(lane_seed), steps,
                                  n_split, 1 + j, (shape, prob)))
            if idx is not None:
                full[:, idx] = d
            else:
                full[:] = d
        else:
            off, u = _layout(n, layout["parts"], layout["pad_multiple"])
            keys = jax.random.split(jax.random.PRNGKey(lane_seed),
                                    layout["parts"])
            mask = np.ones(n, bool)
            if idx is not None:
                mask[:] = False
                mask[idx] = True
            for p in range(layout["parts"]):
                lo, hi = off[p], off[p + 1]
                d = np.asarray(_draws(keys[p], steps, n_split, 1 + j,
                                      ((u,), prob)))
                full[:, lo:hi] = d[:, : hi - lo] & mask[lo:hi]
        out.append((part, full))
    return out


def _deliver(ids: np.ndarray, indptr, tgt, w, n: int) -> np.ndarray:
    """Summed weights onto every target of the sources ``ids`` (exact)."""
    if ids.size == 0:
        return np.zeros(n)
    starts = indptr[ids]
    lens = indptr[ids + 1] - starts
    first = np.cumsum(lens) - lens
    syn = np.repeat(starts - first, lens) + np.arange(lens.sum())
    return np.bincount(tgt[syn], weights=w[syn], minlength=n)


class _Fixed:
    def __init__(self, lif: dict, frac_bits: int):
        self.f = frac_bits
        one = 1 << frac_bits
        ws = lif["w_scale"]
        self.a16 = np.int32(round(lif["dt"] / lif["tau_m"] * (1 << 16)))
        self.gd16 = np.int32(round(lif["dt"] / lif["tau_g"] * (1 << 16)))
        self.vth = np.int32(round(lif["v_th"] / ws * one))
        self.vr = np.int32(round(lif["v_r"] / ws * one))
        self.v0 = np.int32(round(lif["v0"] / ws * one))
        self.ws = np.float32(ws)

    def init(self, shape):
        return (np.full(shape, self.v0, np.int32), np.zeros(shape, np.int32))

    def step(self, v, g, active, g_units, v_mv):
        f = np.int32(self.f)
        g_in = np.round(g_units).astype(np.int32)
        g = np.where(active, g + (g_in << f), g)
        if v_mv is not None:
            v_in = np.round(v_mv.astype(np.float32) / self.ws).astype(np.int32)
            v = np.where(active, v + (v_in << f), v)
        dv = (((self.v0 - v + g) >> np.int32(2)) * self.a16) >> np.int32(14)
        v = np.where(active, v + dv, v)
        g = np.where(active,
                     g - (((g >> np.int32(2)) * self.gd16) >> np.int32(14)), g)
        return v, g, v > self.vth, self.vr, np.int32(0)

    def to_program_units(self, x):
        """Q(frac_bits) -> Q19.12, the units the program reports."""
        return x.astype(np.int64) << (12 - self.f)


class _Float:
    def __init__(self, lif: dict, dtype):
        t = self.t = np.dtype(dtype)
        self.alpha = t.type(lif["dt"] / lif["tau_m"])
        self.decay = t.type(1.0 - lif["dt"] / lif["tau_g"])
        self.vth, self.vr, self.v0 = (t.type(lif["v_th"]), t.type(lif["v_r"]),
                                      t.type(lif["v0"]))
        self.ws = t.type(lif["w_scale"])

    def init(self, shape):
        return np.full(shape, self.v0, self.t), np.zeros(shape, self.t)

    def step(self, v, g, active, g_units, v_mv):
        g_in = g_units.astype(np.float32).astype(self.t) * self.ws
        g = np.where(active, g + g_in, g)
        if v_mv is not None:
            v = np.where(active, v + v_mv.astype(self.t), v)
        v = np.where(active, v + self.alpha * ((self.v0 - v) + g), v)
        g = np.where(active, g * self.decay, g)
        return v, g, v > self.vth, self.vr, self.t.type(0)

    def to_program_units(self, x):
        return x.astype(np.float32)


def run_call(net, model: dict, traffic: dict, lane_seeds, stim_seed: int,
             layout: dict | None = None, frac_bits: int | None = None,
             float_dtype=None) -> Answer:
    """Simulate one call of ``traffic["steps"]`` steps for every lane seed."""
    lif = model["lif"]
    n, steps = net.n, int(traffic["steps"])
    d_steps = max(1, round(lif["delay"] / lif["dt"]))
    ref_steps = np.int32(max(1, round(lif["tau_ref"] / lif["dt"])))
    if model["fixed_point"]:
        arith = _Fixed(lif, 12 if frac_bits is None else frac_bits)
    else:
        arith = _Float(lif, np.float32 if float_dtype is None else float_dtype)
    w = net.out_weights.astype(np.int64)
    if model.get("quantize_bits") is not None:
        b = int(model["quantize_bits"])
        w = np.clip(w, -(1 << (b - 1)), (1 << (b - 1)) - 1)
    w = w.astype(np.float64)
    tr = {**traffic, "dt_ms": lif["dt"]}
    lanes = len(lane_seeds)
    events = [drive_events(n, tr, steps, s, stim_seed, layout)
              for s in lane_seeds]
    v, g = arith.init((lanes, n))
    refrac = np.zeros((lanes, n), np.int32)
    counts = np.zeros((lanes, n), np.int64)
    ring = np.zeros((d_steps, lanes, n), bool)
    brian2 = bool(model["poisson_to_v"])
    amp = 1.5 * lif["v_th"]
    for t in range(steps):
        delayed = ring[t % d_steps]
        g_units = np.stack([_deliver(np.flatnonzero(delayed[b]),
                                     net.out_indptr, net.out_indices, w, n)
                            for b in range(lanes)])
        v_mv, force = None, np.zeros((lanes, n), bool)
        for b in range(lanes):
            for part, draws in events[b]:
                if part["kind"] == "background":
                    force[b] |= draws[t]
                elif brian2:
                    if v_mv is None:
                        v_mv = np.zeros((lanes, n), np.float32)
                    v_mv[b] += draws[t] * np.float32(amp)
                else:
                    g_units[b] += draws[t] * float(model["poisson_weight"])
        active = refrac <= 0
        v, g, above, v_reset, zero = arith.step(v, g, active, g_units, v_mv)
        spikes = active & (above | force)
        v = np.where(spikes, v_reset, v)
        g = np.where(spikes, zero, g)
        refrac = np.where(spikes, ref_steps,
                          np.maximum(refrac - 1, 0)).astype(np.int32)
        ring[t % d_steps] = spikes
        counts += spikes
    return Answer(counts=counts, v=arith.to_program_units(v),
                  g=arith.to_program_units(g), refrac=refrac,
                  dropped=np.zeros(lanes, np.int64))


def run_control_call(net, model: dict, traffic: dict, lane_seeds,
                     stim_seed: int, layout: dict | None = None) -> Answer:
    """The control: the same call one precision step below the
    configuration's (Q19.12 -> 8 fractional bits, float32 -> bfloat16)."""
    lower = ({"frac_bits": 8} if model["fixed_point"]
             else {"float_dtype": BFLOAT16})
    return run_call(net, model, traffic, lane_seeds, stim_seed, layout,
                    **lower)


__all__ = ["BFLOAT16", "run_control_call", "drive_events", "pick", "run_call",
           "stimulus_parts"]
