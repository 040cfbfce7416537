"""Plain reference of the simulated cluster, its controllers and its
metrics, written from the model's equations and independent of the
program: nothing here imports ``repro``.

Semantics (AAPA paper SIV.B-E; ``configs/<config>.json`` gives the
numbers): every simulated minute is 60 one-second ticks. Each tick first
moves the pods that finish their start-up (a FIFO of ``startup_sec``
slots) to ready, then serves a fluid FIFO queue at ``rps_per_replica``
per ready pod, with a congestion-inflated response time, and folds the
utilization into a one-minute EMA. On every ``control_interval_sec``-th
tick the policy decides a replica count from what it observes; scale-ups
start pods at once, scale-downs cancel starting pods first and wait for
the cooldown the previous scale-down asked for. At the end of the minute
the minute's arrivals enter the 60-minute history and the policy's
per-minute hook runs (Holt-Winters update; AAPA's 38 window features,
GBDT archetype, beta calibration and Algorithm 1 every ``stride_min``
minutes).

The device part (``make_lanes``) runs one policy over a block of lanes,
one lane per workload, in the dtype it is given: float32 is the
reference, bfloat16 the control of ``correct``. The metric fold
(``fold``) and the finalization (``finalize``) are NumPy in float64.
The classifier enters as plain arrays (``Classifier``), like weights.
"""
from __future__ import annotations

import json
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

EPS = 1e-9
FEATURE_EPS = 1e-6
CAL_EPS = 1e-6
EDGE_LO_FRAC = 1e-5
# paper Table III, class ids 0 PERIODIC, 1 SPIKE, 2 STATIONARY_NOISY, 3 RAMP
TABLE_III = {"target_cpu": (0.75, 0.30, 0.55, 0.60),
             "cooldown_min": (3.0, 20.0, 12.0, 7.0),
             "min_replicas": (1.0, 2.0, 1.0, 1.0),
             "warm_pool": (0.0, 2.0, 0.0, 0.0)}


class Classifier(NamedTuple):
    """A fitted GBDT + beta calibration as plain arrays."""
    bin_edges: np.ndarray   # [F, bins - 1]
    feat: np.ndarray        # [rounds, K, 2^depth - 1] split feature
    thresh: np.ndarray      # [rounds, K, 2^depth - 1] split bin (right if >)
    leaf: np.ndarray        # [rounds, K, 2^depth]
    base: np.ndarray        # [K]
    cal_a_raw: np.ndarray   # [K]
    cal_b_raw: np.ndarray   # [K]
    cal_c: np.ndarray       # [K]


# ------------------------------------------------------------ features ----
def _acf(x, mean, var, lag):
    n = x.shape[-1]
    xc = x - mean[:, None]
    return jnp.sum(xc[:, :n - lag] * xc[:, lag:], -1) / (n * var
                                                        + FEATURE_EPS)


def window_features(x):
    """The 38 window features of paper SIII.B.1: x [N, 60] -> [N, 38]."""
    dt, e = x.dtype, FEATURE_EPS
    n = x.shape[-1]
    t = jnp.arange(n, dtype=dt)
    mean = jnp.mean(x, -1)
    xc = x - mean[:, None]
    var = jnp.mean(xc ** 2, -1)
    std = jnp.sqrt(var)
    xmin, xmax = jnp.min(x, -1), jnp.max(x, -1)
    xs = jnp.sort(x, -1)

    def quantile(q):
        pos = q * (n - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, n - 1)
        w = pos - lo
        return xs[:, lo] * (1.0 - w) + xs[:, hi] * w

    median, q25, q75 = quantile(0.5), quantile(0.25), quantile(0.75)
    m3 = jnp.mean(xc ** 3, -1)
    m4 = jnp.mean(xc ** 4, -1)
    tbar = (n - 1) / 2.0
    tvar = jnp.mean((t - tbar) ** 2)
    cov = jnp.mean((t - tbar) * xc, -1)
    half = n // 2
    acfs = jnp.stack([_acf(x, mean, var, k) for k in range(2, 31)], -1)
    dx = x[:, 1:] - x[:, :-1]
    mid, left, right = x[:, 1:-1], x[:, :-2], x[:, 2:]
    peaks = (mid > left) & (mid >= right) & (mid > (mean + std)[:, None])
    stat = [mean, std, std / (mean + e), xmin, xmax, median, q25, q75,
            q75 - q25, m3 / (var ** 1.5 + e), m4 / (var ** 2 + e) - 3.0,
            xmax / (median + e), xmax / (mean + e),
            jnp.mean((x <= e).astype(dt), -1), xmax - xmin,
            (cov / tvar) / (mean + e), cov ** 2 / (tvar * var + e),
            (jnp.mean(x[:, half:], -1) + e) / (jnp.mean(x[:, :half], -1) + e),
            _acf(x, mean, var, 1), _acf(x, mean, var, 2),
            _acf(x, mean, var, 3), _acf(x, mean, var, 6),
            _acf(x, mean, var, 12), jnp.max(acfs, -1),
            (jnp.argmax(acfs, -1) + 2).astype(dt) / 30,
            jnp.mean(jnp.abs(dx), -1) / (mean + e),
            jnp.max(jnp.abs(dx), -1) / (mean + e),
            jnp.sum(peaks.astype(dt), -1) / n]

    # spectrum of the centred window without its DC term (rFFT in f32:
    # there is no bfloat16 FFT)
    spec = jnp.abs(jnp.fft.rfft(xc.astype(jnp.float32), axis=-1)) ** 2
    power = spec[:, 1:].astype(dt)
    nb = power.shape[-1]
    total = jnp.sum(power, -1) + e
    p = power / total[:, None]
    idx = jnp.arange(nb)
    top = jnp.sort(power, -1)
    cum = jnp.cumsum(p, -1)
    freq = [-jnp.sum(p * jnp.log(p + e), -1) / np.log(float(nb)),
            jnp.argmax(power, -1).astype(dt) / nb,
            jnp.max(power, -1) / total,
            (top[:, -1] + top[:, -2]) / total,
            jnp.sum(jnp.where(idx < 5, power, 0.0), -1) / total,
            jnp.sum(jnp.where((idx >= 5) & (idx < 15), power, 0.0),
                    -1) / total,
            jnp.sum(jnp.where(idx >= 15, power, 0.0), -1) / total,
            jnp.sum(p * idx.astype(dt), -1) / nb,
            jnp.exp(jnp.mean(jnp.log(power + e), -1))
            / (jnp.mean(power, -1) + e),
            jnp.argmax((cum >= 0.85).astype(jnp.int32), -1).astype(dt) / nb]
    return jnp.stack([a.astype(dt) for a in stat + freq], -1)


def classify(clf: Classifier, feats):
    """GBDT archetype and beta-calibrated confidence: feats [N, F] ->
    (class [N] int32, confidence [N])."""
    dt = feats.dtype
    edges = jnp.asarray(clf.bin_edges, dt)
    bins = jax.vmap(lambda col, ed: jnp.searchsorted(ed, col, side="right"),
                    in_axes=(1, 0), out_axes=1)(feats, edges)
    depth = int(round(np.log2(clf.leaf.shape[-1])))
    R, K, _ = clf.feat.shape
    feat = jnp.asarray(clf.feat, jnp.int32).reshape(R * K, -1)
    thresh = jnp.asarray(clf.thresh, jnp.int32).reshape(R * K, -1)
    leaf = jnp.asarray(clf.leaf, jnp.float32).reshape(R * K, -1)
    rows = jnp.arange(feats.shape[0])

    def one_tree(logits, tree):
        f, th, lv, k = tree
        node = jnp.zeros(feats.shape[0], jnp.int32)
        for d in range(depth):
            at = (1 << d) - 1 + node
            node = 2 * node + (bins[rows, f[at]] > th[at]).astype(jnp.int32)
        return logits.at[:, k].add(lv[node].astype(dt)), None

    logits0 = jnp.broadcast_to(jnp.asarray(clf.base, dt),
                               (feats.shape[0], K))
    logits, _ = jax.lax.scan(one_tree, logits0,
                             (feat, thresh, leaf,
                              jnp.tile(jnp.arange(K), R)))
    p = jax.nn.softmax(logits, axis=-1)
    a = jax.nn.softplus(jnp.asarray(clf.cal_a_raw, dt))
    b = jax.nn.softplus(jnp.asarray(clf.cal_b_raw, dt))
    p = jnp.clip(p, CAL_EPS, 1.0 - CAL_EPS)
    q = jax.nn.sigmoid(a * jnp.log(p) - b * jnp.log1p(-p)
                       + jnp.asarray(clf.cal_c, dt))
    q = q / (jnp.sum(q, -1, keepdims=True) + CAL_EPS)
    return jnp.argmax(q, -1).astype(jnp.int32), jnp.max(q, -1)


# ---------------------------------------------------------- forecasts ----
class HW(NamedTuple):
    level: jax.Array
    trend: jax.Array
    season: jax.Array     # [N, period]
    t: jax.Array          # [] int32 samples seen (the same for every lane)
    resid: jax.Array


def hw_init(n, period, dt):
    z = jnp.zeros(n, dt)
    return HW(z, z, jnp.zeros((n, period), dt), jnp.int32(0), z)


def hw_peak(s: HW, horizon: int):
    """Largest additive Holt-Winters forecast over 1..horizon steps,
    floored at 0."""
    period = s.season.shape[-1]
    hs = jnp.arange(1, horizon + 1)
    phase = (s.t + hs - 1) % period
    preds = (s.level[:, None] + hs.astype(s.level.dtype)[None, :]
             * s.trend[:, None] + s.season[:, phase])
    return jnp.maximum(jnp.max(preds, -1), 0.0)


def hw_update(s: HW, y, fc: dict) -> HW:
    """Observe y: residual EWMA (rate 0.05) of the one-step error, then
    the additive-seasonal triple exponential smoothing step."""
    al, be, ga = fc["alpha"], fc["beta"], fc["gamma"]
    resid = s.resid + 0.05 * (jnp.abs(y - hw_peak(s, 1)) - s.resid)
    phase = s.t % s.season.shape[-1]
    s_t = s.season[:, phase]
    level = al * (y - s_t) + (1.0 - al) * (s.level + s.trend)
    trend = be * (level - s.level) + (1.0 - be) * s.trend
    season = s.season.at[:, phase].set(ga * (y - level) + (1.0 - ga) * s_t)
    return HW(level, trend, season, s.t + 1, resid)


def trend_forecast(x, horizon: int):
    """OLS line through x [N, n] extrapolated `horizon` steps, >= 0."""
    n = x.shape[-1]
    t = jnp.arange(n, dtype=x.dtype)
    tbar = (n - 1) / 2.0
    tvar = jnp.mean((t - tbar) ** 2)
    mean = jnp.mean(x, -1)
    slope = jnp.mean((t - tbar) * (x - mean[:, None]), -1) / tvar
    return jnp.maximum(mean + slope * ((n - 1) - tbar + horizon), 0.0)


def _pick(arch, values, dt):
    out = jnp.full(arch.shape, values[3], dt)
    for k in (2, 1, 0):
        out = jnp.where(arch == k, jnp.asarray(values[k], dt), out)
    return out


# ------------------------------------------------------------ policies ----
def _policy(name: str, cfg: dict, clf: Classifier | None, n: int, dt):
    """(init, decide, on_minute) of policy `name` over n lanes."""
    plant, ctl = cfg["plant"], cfg["controllers"][name]
    fc = cfg["forecaster"]
    rps = plant["rps_per_replica"]
    ci = plant["control_interval_sec"]
    c = lambda v: jnp.asarray(v, dt)  # noqa: E731

    if name == "hpa":
        buf_len = max(int(ctl["stabilization_min"] * 60 / ci), 1)

        def init():
            return jnp.full((n, buf_len), plant["initial_replicas"], dt)

        def decide(buf, o, minute):
            ratio = o["util_ema"] / ctl["target"]
            raw = jnp.ceil(o["total"] * ratio)
            raw = jnp.where(jnp.abs(ratio - 1.0) <= ctl["tolerance"],
                            o["total"], raw)
            idle = ((o["util_ema"] < 0.02) & (o["queue"] <= 0.0)
                    & (o["rate_rps"] <= 1e-6))
            raw = jnp.where(idle, c(0.0), jnp.maximum(raw, 1.0))
            wake = (o["rate_rps"] > 0.0) | (o["queue"] > 0.0)
            raw = jnp.where(wake, jnp.maximum(raw, 1.0), raw)
            buf = jnp.concatenate([buf[:, 1:], raw[:, None]], 1)
            held = jnp.maximum(raw, jnp.max(buf, -1))
            desired = jnp.where(raw >= o["total"], raw, held)
            return buf, desired, jnp.full((n,), ctl["cooldown_min"] * 60.0,
                                          dt)

        def on_minute(buf, hist, minute):
            return buf
        return init, decide, on_minute

    if name == "predictive":
        h = ctl["horizon_min"]

        def init():
            return hw_init(n, fc["period"], dt)

        def decide(s, o, minute):
            per_pod = rps * ctl["target"]
            need_pred = hw_peak(s, h) / 60.0 / per_pod
            need_now = o["rate_rps"] / per_pod
            desired = jnp.ceil(jnp.maximum(need_pred, need_now))
            idle = ((desired < 1.0) & (o["queue"] <= 0.0)
                    & (o["rate_rps"] <= 1e-6))
            desired = jnp.where(idle, c(0.0), jnp.maximum(desired, 1.0))
            return s, desired, jnp.full((n,), ctl["cooldown_min"] * 60.0,
                                        dt)

        def on_minute(s, hist, minute):
            return hw_update(s, hist[:, -1], fc)
        return init, decide, on_minute

    if name == "aapa":
        h, stride = ctl["horizon_min"], ctl["stride_min"]

        def init():
            return (hw_init(n, fc["period"], dt),
                    jnp.full((n,), 2, jnp.int32),                # arch
                    jnp.full((n,), 0.5, dt), jnp.full((n,), 5.0, dt),
                    jnp.full((n,), 1.0, dt))          # cpu, cool, minrep

        def decide(s, o, minute):
            hw, arch, cpu, cool, minrep = s
            cpu_f = jnp.maximum(cpu, 0.05)
            cap = rps * cpu_f
            ratio = o["util_ema"] / cpu_f
            reactive = jnp.ceil(o["total"] * ratio)
            reactive = jnp.where(jnp.abs(ratio - 1.0) <= 0.1, o["total"],
                                 reactive)
            hist = o["history"]
            spike = (jnp.ceil(o["rate_rps"] / cap)
                     + _pick(arch, TABLE_III["warm_pool"], dt) + minrep)
            periodic = jnp.ceil(hw_peak(hw, h) / 60.0 / cap)
            ramp = jnp.ceil(jnp.maximum(trend_forecast(hist[:, -30:], h)
                                        / 60.0, o["rate_rps"]) / cap)
            stat = jnp.ceil(jnp.mean(hist[:, -15:], -1) / 60.0 / cap)
            strat = jnp.where(arch == 0, periodic,
                              jnp.where(arch == 1, spike,
                                        jnp.where(arch == 2, stat, ramp)))
            desired = jnp.maximum(jnp.maximum(reactive, strat),
                                  jnp.maximum(minrep, 1.0))
            return s, desired, cool * 60.0

        def on_minute(s, hist, minute):
            hw, arch, cpu, cool, minrep = s
            hw = hw_update(hw, hist[:, -1], fc)

            def reclassify(_):
                k, conf = classify(clf, window_features(hist))
                conf = jnp.clip(conf, 0.0, 1.0)
                m = 1.0 + 0.5 * (1.0 - conf)
                return (k,
                        _pick(k, TABLE_III["target_cpu"], dt)
                        * (1.0 - 0.2 * (1.0 - conf)),
                        _pick(k, TABLE_III["cooldown_min"], dt) * m,
                        jnp.ceil(_pick(k, TABLE_III["min_replicas"], dt)
                                 * m))

            arch, cpu, cool, minrep = jax.lax.cond(
                minute % stride == 0, reclassify,
                lambda _: (arch, cpu, cool, minrep), None)
            return (hw, arch, cpu, cool, minrep)
        return init, decide, on_minute

    raise KeyError(f"the reference has no policy {name!r}")


# --------------------------------------------------------------- plant ----
def make_lanes(policy: str, cfg: dict, clf: Classifier | None, n: int,
               minutes: int, dtype=jnp.float32):
    """jit: rates [n, minutes] -> per-minute aggregates {field: [minutes,
    n]} (served, violated, cold, replica_sec, resp_sum, util_mean, ups,
    downs, osc), one lane per workload under `policy`."""
    p = cfg["plant"]
    dt = jnp.dtype(dtype)
    S, ci = int(p["startup_sec"]), int(p["control_interval_sec"])
    rps, svc = p["rps_per_replica"], p["service_sec"]
    init, decide, on_minute = _policy(policy, cfg, clf, n, dt)

    def flow(ready, queue, wait, ema, arr):
        cap = ready * rps
        work = queue + arr
        served = jnp.minimum(work, cap)
        new_q = work - served
        aged = wait + queue
        mean_age = aged / jnp.maximum(work, EPS)
        wait = aged * new_q / jnp.maximum(work, EPS)
        util = served / jnp.maximum(cap, EPS)
        resp = (svc / jnp.maximum(1.0 - util, 0.05) + mean_age
                + (0.5 * new_q) / jnp.maximum(cap, EPS))
        resp = jnp.minimum(resp, p["resp_cap_sec"])
        resp = jnp.where(served > 0, resp, 0.0)
        violated = jnp.where(resp > p["slo_sec"], served, 0.0)
        cold = jnp.where(ready < 0.5, arr, 0.0)
        ema = ema + (util - ema) / p["metric_tau_sec"]
        return new_q, wait, ema, served, violated, cold, resp, util

    def pop(ready, pipe, pipe_sum):
        first = pipe[:, 0]
        pipe = jnp.concatenate([pipe[:, 1:], jnp.zeros((n, 1), dt)], 1)
        return ready + first, pipe, jnp.maximum(pipe_sum - first, 0.0)

    def minute(carry, rate):
        st, ctrl, hist, m = carry
        ready, pipe, pipe_sum, queue, wait, ema, cool, last = st
        arr = rate / 60.0
        acc = {k: jnp.zeros(n, dt) for k in ("served", "violated", "cold",
                                             "total", "resp_w", "ups",
                                             "downs", "osc", "util")}

        def fold(acc, served, violated, cold, total, resp, util, up=None,
                 down=None, osc=None):
            acc = dict(acc)
            acc["served"] += served
            acc["violated"] += violated
            acc["cold"] += cold
            acc["total"] += total
            acc["resp_w"] += jnp.where(served > 0, resp * served, 0.0)
            acc["util"] += util
            if up is not None:
                acc["ups"] += up
                acc["downs"] += down
                acc["osc"] += osc
            return acc

        def plant_tick(_, c):
            ready, pipe, pipe_sum, queue, wait, ema, acc = c
            ready, pipe, pipe_sum = pop(ready, pipe, pipe_sum)
            queue, wait, ema, served, violated, cold, resp, util = flow(
                ready, queue, wait, ema, arr)
            acc = fold(acc, served, violated, cold, ready + pipe_sum, resp,
                       util)
            return ready, pipe, pipe_sum, queue, wait, ema, acc

        for head in range(0, 60, ci):
            # the decision tick
            ready, pipe, pipe_sum = pop(ready, pipe, pipe_sum)
            queue, wait, ema, served, violated, cold, resp, util = flow(
                ready, queue, wait, ema, arr)
            total = ready + pipe_sum
            obs = {"total": total, "util_ema": ema, "queue": queue,
                   "rate_rps": arr, "history": hist}
            ctrl, desired, cool_req = decide(ctrl, obs, m)
            desired = jnp.clip(desired, 0.0, p["max_replicas"])
            up = desired > total + 0.5
            down = (desired < total - 0.5) & (cool <= 0.0)
            add = jnp.where(up, desired - total, 0.0)
            remove = jnp.where(down, total - desired, 0.0)
            now = jnp.where(up, 1.0, jnp.where(down, -1.0, 0.0)).astype(dt)
            osc = ((now != 0) & (last != 0) & (now != last)).astype(dt)
            last = jnp.where(now != 0, now, last)
            cool = jnp.where(down, cool_req, jnp.maximum(cool - 1.0, 0.0))
            # starts join the pipeline's tail; removals cancel starting
            # pods first (proportionally), then ready ones
            pipe = jnp.concatenate([pipe[:, :-1], pipe[:, -1:]
                                    + add[:, None]], 1)
            pipe_sum = pipe_sum + add
            from_pipe = jnp.minimum(remove, pipe_sum)
            factor = 1.0 - from_pipe / jnp.maximum(pipe_sum, EPS)
            pipe = pipe * factor[:, None]
            pipe_sum = pipe_sum * factor
            ready = jnp.maximum(ready - (remove - from_pipe), 0.0)
            acc = fold(acc, served, violated, cold, ready + pipe_sum, resp,
                       util, up.astype(dt), down.astype(dt), osc)
            # the ticks until the next decision: plant only; the
            # cooldown runs down one second a tick
            ticks = min(ci, 60 - head) - 1
            ready, pipe, pipe_sum, queue, wait, ema, acc = jax.lax.fori_loop(
                0, ticks, plant_tick,
                (ready, pipe, pipe_sum, queue, wait, ema, acc))
            cool = jnp.maximum(cool - float(ticks), 0.0)

        hist = jnp.concatenate([hist[:, 1:], rate[:, None]], 1)
        ctrl = on_minute(ctrl, hist, m + 1)
        out = {"served": acc["served"], "violated": acc["violated"],
               "cold": acc["cold"], "replica_sec": acc["total"],
               "resp_sum": acc["resp_w"], "util_mean": acc["util"] / 60.0,
               "ups": acc["ups"], "downs": acc["downs"], "osc": acc["osc"]}
        st = (ready, pipe, pipe_sum, queue, wait, ema, cool, last)
        return (st, ctrl, hist, m + 1), out

    def run(rates):
        rates = rates.astype(dt)
        z = jnp.zeros(n, dt)
        st = (jnp.full((n,), p["initial_replicas"], dt),
              jnp.zeros((n, S), dt), z, z, z, jnp.full((n,), 0.5, dt), z, z)
        carry = (st, init(), jnp.zeros((n, int(p["history_len"])), dt),
                 jnp.int32(0))
        _, out = jax.lax.scan(minute, carry, rates.T)
        return out

    return jax.jit(run)


# -------------------------------------------------------------- metrics ----
def response_edges(bins: int, cap: float) -> np.ndarray:
    """Log-spaced response-histogram edges in seconds (float32 values)."""
    return np.geomspace(cap * EDGE_LO_FRAC, cap, bins).astype(np.float32)


def fold(out: dict, edges: np.ndarray, *, per_lane_hist: bool) -> dict:
    """Per-minute outputs {field: [M, n]} -> per-lane metric accumulators
    {field: [n]} in float64, with the served-weighted histogram of the
    minutes' mean response time per lane ([n, bins]) or summed over the
    lanes ([bins])."""
    o = {k: np.asarray(jnp.asarray(v, jnp.float32), np.float64)
         for k, v in out.items()}
    served = o["served"]
    resp = np.where(served > 0, o["resp_sum"] / np.maximum(served, EPS), 0.0)
    bins = len(edges)
    idx = np.clip(np.searchsorted(edges.astype(np.float64), resp,
                                  side="left"), 0, bins - 1)
    M, n = served.shape
    if per_lane_hist:
        flat = (np.arange(n)[None, :] * bins + idx).ravel()
        hist = np.bincount(flat, served.ravel(),
                           minlength=n * bins).reshape(n, bins)
    else:
        hist = np.bincount(idx.ravel(), served.ravel(), minlength=bins)
    acc = {k: o[k].sum(0) for k in ("served", "violated", "cold",
                                    "replica_sec", "resp_sum", "ups",
                                    "downs", "osc")}
    acc["util_sum"] = o["util_mean"].sum(0)
    acc["over_cnt"] = (o["util_mean"] < 0.5).sum(0).astype(np.float64)
    acc["minutes"] = np.full(n, float(M))
    acc["hist"] = hist
    return acc


def finalize(acc: dict, edges: np.ndarray) -> dict:
    """Accumulators (any leading shape, hist bins last) -> the episode
    metrics of paper SIV.D."""
    e = edges.astype(np.float64)
    rep = np.concatenate([e[:1], np.sqrt(e[:-1] * e[1:])])
    arrived = np.maximum(acc["served"], 1.0)
    actions = acc["ups"] + acc["downs"]

    def quantile(q):
        cum = np.cumsum(acc["hist"], -1)
        total = cum[..., -1]
        k = np.clip((cum < np.maximum(q * total, EPS)[..., None]).sum(-1),
                    0, len(e) - 1)
        return np.where(total > 0, rep[k], 0.0)

    return {"slo_violation_rate": acc["violated"] / arrived,
            "cold_start_rate": acc["cold"] / arrived,
            "mean_response_ms": 1e3 * acc["resp_sum"] / arrived,
            "p95_response_ms": 1e3 * quantile(0.95),
            "p99_response_ms": 1e3 * quantile(0.99),
            "replica_minutes": acc["replica_sec"] / 60.0,
            "avg_cpu_util": acc["util_sum"] / np.maximum(acc["minutes"], 1),
            "overprovision_rate": acc["over_cnt"]
            / np.maximum(acc["minutes"], 1),
            "scaling_actions": actions, "oscillations": acc["osc"],
            "mean_action_interval_min": acc["minutes"]
            / np.maximum(actions, 1.0),
            "total_requests": acc["served"]}


_PROGRAMS: dict = {}


def _digest(clf: Classifier | None) -> str:
    if clf is None:
        return ""
    import hashlib
    h = hashlib.sha256()
    for a in clf:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Reference:
    """The reference of one configuration, in one dtype: one compiled
    program per (policy, block, minutes), kept for the process."""

    def __init__(self, cfg: dict, clf: Classifier | None, *,
                 dtype=jnp.float32, block: int = 25_000, devices=None):
        self.cfg, self.clf, self.dtype, self.block = cfg, clf, dtype, block
        self.devices = list(devices or jax.devices()[:1])
        self.edges = response_edges(int(cfg["metric_bins"]),
                                    cfg["plant"]["resp_cap_sec"])
        self._key = (json.dumps(cfg, sort_keys=True), _digest(clf),
                     jnp.dtype(dtype).name)

    def lanes(self, policy: str, rates: np.ndarray, *,
              per_lane_hist: bool) -> dict:
        """`fold` of `policy` over rates [W, M], computed in blocks of at
        most `block` lanes spread over `devices`; scalar fields [W], hist
        [W, bins] or [bins]."""
        W, M = rates.shape
        block = min(self.block, W)
        key = self._key + (policy, block, M)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = make_lanes(policy, self.cfg, self.clf, block,
                                        M, self.dtype)
        pending = []
        for i, lo in enumerate(range(0, W, block)):
            r = np.asarray(rates[lo:lo + block], np.float32)
            live = r.shape[0]
            if live < block:
                r = np.concatenate([r, np.zeros((block - live, M),
                                                np.float32)])
            dev = self.devices[i % len(self.devices)]
            pending.append((live, _PROGRAMS[key](jax.device_put(r, dev))))
        parts = [fold({k: v[:, :live] for k, v in out.items()}, self.edges,
                      per_lane_hist=per_lane_hist)
                 for live, out in pending]
        acc = {k: np.concatenate([pt[k] for pt in parts])
               for k in parts[0] if k != "hist"}
        acc["hist"] = (np.concatenate([pt["hist"] for pt in parts])
                       if per_lane_hist else sum(pt["hist"] for pt in parts))
        return acc
