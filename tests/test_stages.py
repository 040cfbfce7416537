"""The lane core's stage scopes and the streaming feed's host spans
(``repro.obs.stages``), read as the benchmark's trace reduction reads
them (``perfbench.stage_trace``). CPU only: the compiled HLO's op-name
metadata is what a TPU trace carries as each op's ``tf_op``."""
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import classifier, stage_trace, trace
from perfbench.tests import fakes
from repro.evals import fleet, matrix
from repro.evals import metrics as EM
from repro.obs import stages

REPO = pathlib.Path(__file__).resolve().parents[1]
OP_NAME = re.compile(r'op_name="([^"]*)"')
PATTERN = stage_trace.stage_pattern(stages.STAGES)


def test_stage_scopes_only_known_names():
    assert len(set(stages.STAGES)) == len(stages.STAGES) == 5
    for name in stages.STAGES:
        with stages.stage(name):
            pass
    with pytest.raises(ValueError, match="unknown lane stage"):
        stages.stage("lane.plants")


def test_innermost_stage_of_an_op_path():
    p, of, none = PATTERN, stage_trace.stage_of, stage_trace.UNSCOPED
    assert of("jit(run)/while/body/lane.decide/vmap(lane.forecast)/"
              "gather:", p) == stages.FORECAST
    assert of("jit(run)/lane.decide/vmap()/cond/branch_1_fun/"
              "lane.reclassify/jit(_take)/gather", p) == stages.RECLASSIFY
    assert of("jit(run)/lane.plantx/add", p) == none
    assert of("jit(run)/while/body/add", p) == none
    assert of("jit(run)/lane.plant/add", None) == none


@pytest.fixture(scope="module")
def programs():
    """The optimized HLO of the three entry programs of the benchmark's
    cells, at test size: the fleet runner and the streaming fold
    (``hpa`` + ``aapa``) and the matrix runner (``hpa``, ``predictive``,
    ``aapa``), with a random GBDT classifier."""
    cfg = json.loads((REPO / "perfbench/configs/k8s_fig2_3pol.json")
                     .read_text())
    classify = classifier.program_classify(fakes.random_classifier())
    sim, bins = dict(cfg["plant"]), int(cfg["metric_bins"])
    fs = fleet.spec("t", policies=("hpa", "aapa"), n_workloads=16,
                    w_chunk=8, minutes=30, sim=sim, bins=bins)
    acc = jax.tree.map(lambda a: jnp.broadcast_to(a, (2,) + a.shape),
                       EM.accum_init(bins))
    ms = matrix.spec("t", policies=("hpa", "predictive", "aapa"),
                     scenarios=(("archetype_pure", {"kind": "SPIKE"}),),
                     seeds=(0, 1), n_workloads=4, minutes=40, sim=sim,
                     bins=bins)
    rates = np.zeros((1, 2, 4, 40), np.float32)
    return {
        "fleet_runner": fleet.make_fleet_runner(fs, classify, donate=False)
        .lower(np.zeros((2, 8, 30), np.float32)).compile().as_text(),
        "chunk_folder": fleet._jit_fold(fs, classify)
        .lower(acc, np.zeros((8, 30), np.float32)).compile().as_text(),
        "matrix_runner": matrix.make_runner(ms, classify).lower(rates)
        .compile().as_text()}


def _stages_of(hlo: str, keep=lambda op_name: True) -> set:
    return {stage_trace.stage_of(n, PATTERN) for n in OP_NAME.findall(hlo)
            if keep(n)}


@pytest.mark.parametrize("program", ["fleet_runner", "chunk_folder",
                                     "matrix_runner"])
def test_every_stage_names_ops_of_the_compiled_program(programs, program):
    assert set(stages.STAGES) <= _stages_of(programs[program])


@pytest.mark.parametrize("program", ["fleet_runner", "chunk_folder",
                                     "matrix_runner"])
def test_gbdt_gather_and_fold_searchsorted_resolve_to_their_stages(
        programs, program):
    hlo = programs[program]
    # the GBDT row evaluator, and in it the node-table pick of the
    # binned features (a one-hot product where the host path gathers)
    row = _stages_of(hlo, lambda n: "row_logits" in n)
    assert row == {stages.RECLASSIFY}
    node_pick = _stages_of(hlo, lambda n: "row_logits" in n
                           and n.endswith("dot_general"))
    assert node_pick == {stages.RECLASSIFY}
    # the metric fold's histogram searchsorted (binning the features is
    # a searchsorted of reclassification's own)
    fold_search = _stages_of(hlo, lambda n: "searchsorted" in n
                             and "row_logits" not in n)
    assert fold_search == {stages.METRIC_FOLD}


def test_chunk_folder_spans_each_chunk_in_a_profiler_trace(tmp_path):
    """Under ``jax.profiler.trace`` the streaming fold writes one
    ``fleet.put`` and then one ``fleet.fold`` host span per chunk, which
    the benchmark's reduction reads."""
    fs = fleet.spec("t", policies=("hpa",), n_workloads=12, w_chunk=4,
                    minutes=6)
    fold = fleet.make_chunk_folder(fs)
    chunks = list(fleet.rate_chunks(fs))

    def acc0():
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (1,) + a.shape),
                            EM.accum_init(fs.bins))
    jax.block_until_ready(fold(acc0(), chunks[0]))        # compile
    acc = acc0()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for chunk in chunks:
                acc = fold(acc, chunk)
            jax.block_until_ready(acc)
    pb, = tmp_path.rglob("*.xplane.pb")
    s = stage_trace.load(pb, stages.STAGES, stages.HOST_SPANS)
    names = [n for n, _, _ in sorted(s.spans, key=lambda sp: sp[1])]
    assert names == [stages.FLEET_PUT, stages.FLEET_FOLD] * len(chunks)
    assert all(d > 0 for d in s.span_durations(stages.FLEET_FOLD))
