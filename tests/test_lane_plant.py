"""Per-lane plant parameters (``sim.cluster.LanePlant``): a fleet whose
workloads each have their own capacity, service time and SLO, carried
through the fleet runner, the streaming fold and the lane core, against
``simulate_reference`` run on each lane with its own scalar
``SimConfig``."""
import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from perfbench import classifier, stage_trace
from perfbench.tests import fakes
from repro.evals import fleet, matrix
from repro.evals import metrics as EM
from repro.obs import stages
from repro.scaling import registry
from repro.sim.cluster import (LanePlant, SimConfig, make_simulator,
                               simulate, simulate_reference)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CFG = SimConfig()
POLICIES = ("hpa", "aapa", "predictive")
W, WC, M = 16, 8, 40
RTOL = 2e-6        # the repo's parity tolerance for compiled programs


@pytest.fixture(scope="module")
def classify():
    return classifier.program_classify(fakes.random_classifier())


def _fleet(seed=0):
    """W workloads with distinct plants: service times over three
    decades, capacity 2 / service, SLO 5 x service, and rates that need
    from under one replica to tens of them."""
    rng = np.random.default_rng(seed)
    svc = 10.0 ** rng.uniform(-2.0, 1.0, W)
    need = 10.0 ** rng.uniform(-1.5, 1.5, W)     # replicas at the mean
    mean = need * 2.0 / svc * 60.0               # invocations per minute
    wave = 1.0 + 0.8 * np.sin(np.arange(M) / 5.0 + rng.uniform(0, 6, (W, 1)))
    rates = rng.poisson(mean[:, None] * wave).astype(np.float32)
    plant = LanePlant(*(np.asarray(a, np.float32)
                        for a in (2.0 / svc, svc, 5.0 * svc)))
    return rates, plant


def _spec():
    return fleet.spec("t_lane_plant", policies=POLICIES, n_workloads=W,
                      w_chunk=WC, minutes=M)


def _chunks(plant):
    return LanePlant(*(a.reshape(W // WC, WC) for a in plant))


def _reference_accums(rates, plant, classify):
    """Per policy and lane: `simulate_reference` on the lane alone with
    the lane's own scalar SimConfig, folded by `EM.accum_update`;
    MetricAccum of [P, W] leaves."""
    edges = EM.response_edges(EM.DEFAULT_BINS, CFG.resp_cap_sec)
    per_policy = []
    for name in POLICIES:
        lanes = []
        for w in range(W):
            cfg = dataclasses.replace(
                CFG, rps_per_replica=float(plant.rps_per_replica[w]),
                service_sec=float(plant.service_sec[w]),
                slo_sec=float(plant.slo_sec[w]))
            ctrl = registry.get_controller(name, cfg, classify=classify)

            @jax.jit
            def one(r, ctrl=ctrl, cfg=cfg):
                out = simulate_reference(r, ctrl, cfg)
                acc, _ = jax.lax.scan(
                    lambda a, m: (EM.accum_update(a, m, edges), None),
                    EM.accum_init(EM.DEFAULT_BINS), out)
                return acc
            lanes.append(one(rates[w]))
        per_policy.append(jax.tree.map(lambda *x: jnp.stack(x), *lanes))
    return jax.tree.map(lambda *x: np.asarray(jnp.stack(x)), *per_policy)


@pytest.fixture(scope="module")
def lane_fleet(classify):
    rates, plant = _fleet()
    return rates, plant, _reference_accums(rates, plant, classify)


def _assert_accums(got, want, err):
    for f in EM.MetricAccum._fields:
        np.testing.assert_allclose(np.asarray(getattr(got, f)),
                                   np.asarray(getattr(want, f)),
                                   rtol=RTOL, atol=1e-5,
                                   err_msg=f"{err}: {f}")


def test_lane_core_equals_each_lanes_own_scalar_reference(lane_fleet,
                                                          classify):
    """Lane by lane: the lane core over all W lanes at once, each with
    its own plant, gives what `simulate_reference` gives each lane with
    that lane's scalar SimConfig."""
    rates, plant, want = lane_fleet
    spec = _spec()
    edges = EM.response_edges(spec.bins, CFG.resp_cap_sec)
    lanes = matrix._lane_runner(fleet.controllers(spec, classify), CFG,
                                edges, per_workload=True)
    got = jax.jit(lanes)(rates, plant)
    _assert_accums(got, want, "lane core")
    # the plants differ enough that the lanes' outcomes do
    assert len(np.unique(np.round(want.replica_sec[0] / rates.sum(1), 6))) \
        == W


@pytest.mark.parametrize("mode", ["fleet_runner", "chunk_folder"])
def test_fleet_with_lane_plant_equals_summed_lane_references(
        lane_fleet, classify, mode):
    """The one-dispatch runner ([C, Wc] plant chunks beside the rates)
    and the streaming fold ([Wc] per chunk) pool the same sums as the
    per-lane references."""
    rates, plant, want = lane_fleet
    spec = _spec()
    chunks, pchunks = rates.reshape(W // WC, WC, M), _chunks(plant)
    if mode == "fleet_runner":
        got = fleet.make_fleet_runner(spec, classify)(chunks, pchunks)
    else:
        fold = fleet.make_chunk_folder(spec, classify)
        got = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (len(POLICIES),) + a.shape),
            EM.accum_init(spec.bins))
        for c in range(W // WC):
            got = fold(got, chunks[c],
                       LanePlant(*(a[c] for a in pchunks)))
    pooled = jax.tree.map(lambda a: a.astype(np.float64).sum(1), want)
    _assert_accums(got, pooled, mode)


@pytest.mark.parametrize("mode", ["fleet_runner", "chunk_folder",
                                  "lane_core"])
def test_uniform_lane_plant_gives_the_scalar_path(classify, mode):
    """A per-lane plant that holds the SimConfig numbers in every lane
    gives the scalar plant's results."""
    rates, _ = _fleet(seed=1)
    uniform = LanePlant(*(np.full(W, v, np.float32) for v in (
        CFG.rps_per_replica, CFG.service_sec, CFG.slo_sec)))
    spec = _spec()
    chunks = rates.reshape(W // WC, WC, M)
    if mode == "fleet_runner":
        run = fleet.make_fleet_runner(spec, classify, donate=False)
        got, want = run(chunks, _chunks(uniform)), run(chunks)
    elif mode == "chunk_folder":
        fold = fleet._jit_fold(spec, classify)

        def acc0():       # the fold donates its accumulator
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (len(POLICIES),) + a.shape),
                EM.accum_init(spec.bins))
        got = fold(acc0(), chunks[0],
                   LanePlant(*(a[:WC] for a in uniform)))
        want = fold(acc0(), chunks[0])
    else:
        edges = EM.response_edges(spec.bins, CFG.resp_cap_sec)
        lanes = jax.jit(matrix._lane_runner(
            fleet.controllers(spec, classify), CFG, edges))
        got, want = lanes(rates, uniform), lanes(rates)
    _assert_accums(got, want, mode)


@pytest.mark.parametrize("policy", registry.available())
def test_single_lane_simulate_takes_the_lane_plant(policy, classify):
    """`simulate` and `simulate_reference` with a LanePlant of scalars
    equal the runs with that lane's scalar SimConfig, and
    `make_simulator` maps a [W] plant over its lanes."""
    kw = (dict(classify=classify)
          if registry.spec(policy).needs_classifier else {})
    rates, plant = _fleet(seed=2)
    sims = make_simulator(registry.get_controller(policy, CFG, **kw),
                          CFG, w_chunk=WC)(jnp.asarray(rates), plant)
    for w in (0, 5, 11):
        lane = LanePlant(*(a[w] for a in plant))
        cfg = dataclasses.replace(
            CFG, rps_per_replica=float(lane.rps_per_replica),
            service_sec=float(lane.service_sec),
            slo_sec=float(lane.slo_sec))
        own = simulate_reference(rates[w], registry.get_controller(
            policy, cfg, **kw), cfg)
        ctrl = registry.get_controller(policy, CFG, **kw)
        for got in (simulate(rates[w], ctrl, CFG, plant=lane),
                    simulate_reference(rates[w], ctrl, CFG, plant=lane),
                    jax.tree.map(lambda a: a[w], sims)):
            for f in got._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(got, f)), np.asarray(getattr(own, f)),
                    rtol=RTOL, atol=1e-5, err_msg=f)


# ------------------------------------------- stages of the plant reads ----
#: primitives that move a value without computing with it
_MOVES = {"broadcast_in_dim", "reshape", "convert_element_type", "squeeze",
          "expand_dims", "sharding_constraint", "slice", "dynamic_slice",
          "concatenate", "copy", "copy_p", "transpose"}


def _sub_jaxprs(eqn):
    """(sub-jaxpr, eqn operands matching its invars, eqn results
    matching its outvars) of a higher-order equation."""
    p, name = eqn.params, eqn.primitive.name
    if name == "scan":
        return [(p["jaxpr"].jaxpr, eqn.invars, eqn.outvars)]
    if name == "while":
        return [(p["body_jaxpr"].jaxpr, eqn.invars[p["cond_nconsts"]:],
                 eqn.outvars)]
    if name == "cond":
        return [(b.jaxpr, eqn.invars[1:], eqn.outvars)
                for b in p["branches"]]
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            return [(getattr(p[key], "jaxpr", p[key]), eqn.invars,
                     eqn.outvars)]
    return []


def _plant_reads(jaxpr, held: set, out: list) -> None:
    """Append every equation that computes with a value in `held` (the
    plant's arrays, followed through moves and into sub-jaxprs)."""
    def holds(v):
        return not isinstance(v, jcore.Literal) and v in held
    for eqn in jaxpr.eqns:
        if not any(holds(v) for v in eqn.invars):
            continue
        subs = _sub_jaxprs(eqn)
        for sub, ins, outs in subs:
            inner = {s for s, v in zip(sub.invars, ins) if holds(v)}
            _plant_reads(sub, inner, out)
            held.update(o for o, so in zip(outs, sub.outvars) if so in inner)
        if subs:
            continue
        if eqn.primitive.name in _MOVES:
            held.update(eqn.outvars)
        else:
            out.append(eqn)


def _repo_frame(eqn):
    """(file, line) of the innermost frame in the program's source."""
    src = os.path.join(ROOT, "src")
    for f in eqn.source_info.traceback.frames:
        if os.path.normpath(f.file_name).startswith(src):
            return os.path.normpath(f.file_name), f.line_num
    return None


def _hlo_ops_by_frame(hlo: str) -> dict:
    """(file, line) of each op's stack frame -> its op names, from the
    compiled HLO's metadata and its frame tables."""
    tables = {}
    for block in hlo.split("\n\n"):
        head, _, body = block.partition("\n")
        tables[head] = dict(re.findall(r"^(\d+) (.*)$", body, re.M))
    files = {k: os.path.normpath(json.loads(v))
             for k, v in tables["FileNames"].items()}
    locs = {k: (files[re.search(r"file_name_id=(\d+)", v)[1]],
                int(re.search(r"\bline=(\d+)", v)[1]))
            for k, v in tables["FileLocations"].items()}
    frames = {k: locs[re.search(r"file_location_id=(\d+)", v)[1]]
              for k, v in tables["StackFrames"].items()}
    ops: dict = {}
    for name, frame in re.findall(
            r'op_name="([^"]*)" stack_frame_id=(\d+)', hlo):
        ops.setdefault(frames[frame], set()).add(name)
    return ops


def test_lane_plant_reads_resolve_to_plant_and_decide_stages(classify):
    """Every op that computes with a lane's plant value (the fluid
    queue's capacity and service time, the SLO test, and the
    controllers' capacity terms) lies in ``lane.plant`` or
    ``lane.decide`` in the compiled fleet runner, as the trace reduction
    reads it; both stages have such ops, and nothing else does."""
    spec = _spec()
    run = fleet.make_fleet_runner(spec, classify, donate=False)
    rates = np.zeros((W // WC, WC, M), np.float32)
    plant = _chunks(_fleet()[1])
    closed = jax.make_jaxpr(run)(rates, plant)
    reads: list = []
    _plant_reads(closed.jaxpr, set(closed.jaxpr.invars[1:]), reads)
    frames = {_repo_frame(e) for e in reads}
    assert None not in frames and len(frames) >= 4, frames
    hlo = run.lower(rates, plant).compile().as_text()
    by_frame = _hlo_ops_by_frame(hlo)
    pattern = stage_trace.stage_pattern(stages.STAGES)
    found = {stage_trace.stage_of(n, pattern)
             for fr in frames for n in by_frame.get(fr, ())}
    assert found == {stages.PLANT, stages.DECIDE}, found
    assert all(fr in by_frame for fr in frames), frames - set(by_frame)


def test_sharded_lane_plant_fleet_matches_unsharded_8dev():
    """The per-lane plant shards with the lanes over an 8-device dp mesh
    (``constrain_lanes``): pooled sums bit-close to one device."""
    code = textwrap.dedent("""
        import json
        import numpy as np, jax
        from repro.dist import sharding as shd
        from repro.evals import fleet
        from repro.sim.cluster import LanePlant

        rng = np.random.default_rng(0)
        svc = (10.0 ** rng.uniform(-2, 1, 32)).astype(np.float32)
        rates = rng.poisson(10.0 ** rng.uniform(0, 4, (32, 1))
                            * np.ones((1, 30))).astype(np.float32)
        plant = LanePlant(*(a.reshape(2, 16) for a in
                            (2.0 / svc, svc, 5.0 * svc)))
        spec = fleet.spec("t", policies=("hpa", "predictive"),
                          n_workloads=32, w_chunk=16, minutes=30)
        one = fleet.make_fleet_runner(spec)(rates.reshape(2, 16, 30),
                                            plant)
        mesh = shd.make_mesh((8,), ("data",))
        shd.set_mesh(mesh)
        with mesh:
            many = fleet.make_fleet_runner(spec)(rates.reshape(2, 16, 30),
                                                 plant)
        err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))
                               / np.maximum(np.abs(np.asarray(a)), 1.0)))
                  for a, b in zip(one, many))
        print(json.dumps({"err": err, "n_devices": jax.device_count()}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 8, res
    assert res["err"] < RTOL, res
