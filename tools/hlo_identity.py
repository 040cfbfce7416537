"""Compare the optimized HLO of the benchmark's three entry programs
between two checkouts, with op metadata set aside.

    JAX_PLATFORMS=cpu python tools/hlo_identity.py <old tree> <new tree> [--tpu]

Each tree compiles, in a process of its own, the fleet runner, the
streaming fold and the matrix runner of ``repro.evals`` at test size
(``hpa`` + ``aapa``, and ``hpa``, ``predictive``, ``aapa`` for the
matrix, with a random GBDT classifier), for the CPU or, with ``--tpu``,
for a described TPU v5e (no chip needed). The texts are compared twice:
with every ``metadata={...}`` and the stack-frame tables removed, and
then also with every instruction and computation name replaced by its
order of first appearance, since XLA derives those names from the op
names that ``jax.named_scope`` sets. Prints the count of differing
lines per program and exits 1 if the renamed texts differ.
"""
import difflib
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

PROGRAMS = ("fleet_runner", "chunk_folder", "matrix_runner")
META = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
NAME = re.compile(r"\b([A-Za-z_][\w-]*(?:\.[\w-]+)*\.\d+)\b")

DUMP = r'''
import json, pathlib, sys
root, out, tpu = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2]), \
    sys.argv[3] == "1"
sys.path[:0] = [str(root), str(root / "src")]
import numpy as np, jax, jax.numpy as jnp
from perfbench import classifier
from perfbench.tests import fakes
from repro.evals import fleet, matrix
from repro.evals import metrics as EM
if tpu:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    arg = lambda shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=SingleDeviceSharding(dev))
else:
    arg = lambda shape: np.zeros(shape, np.float32)
classify = classifier.program_classify(fakes.random_classifier())
cfg = json.loads((root / "perfbench/configs/k8s_fig2_3pol.json").read_text())
sim, bins = dict(cfg["plant"]), int(cfg["metric_bins"])
fs = fleet.spec("t", policies=("hpa", "aapa"), n_workloads=16, w_chunk=8,
                minutes=30, sim=sim, bins=bins)
fold = getattr(fleet, "_jit_fold", fleet.make_chunk_folder)(fs, classify)
acc = jax.tree.map(lambda a: arg((2,) + a.shape), EM.accum_init(bins))
ms = matrix.spec("t", policies=("hpa", "predictive", "aapa"),
                 scenarios=(("archetype_pure", {"kind": "SPIKE"}),
                            ("archetype_pure", {"kind": "RAMP"})),
                 seeds=(0, 1), n_workloads=4, minutes=40, sim=sim, bins=bins)
texts = {
    "fleet_runner": fleet.make_fleet_runner(fs, classify).lower(
        arg((2, 8, 30))),
    "chunk_folder": fold.lower(acc, arg((8, 30))),
    "matrix_runner": matrix.make_runner(ms, classify).lower(
        arg((2, 2, 4, 40)))}
for name, lowered in texts.items():
    (out / f"{name}.hlo").write_text(lowered.compile().as_text())
'''


def strip(text: str, rename: bool) -> list[str]:
    blocks = text.split("\n\n")
    text = "\n\n".join(b for b in blocks
                       if b.split("\n", 1)[0] not in TABLES)
    text = META.sub("", text)
    if rename:
        ids = {}
        text = NAME.sub(lambda m: "n%d" % ids.setdefault(m.group(1),
                                                           len(ids)), text)
    return text.splitlines()


def dump(tree: str, out: pathlib.Path, tpu: bool) -> None:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.setdefault("TPU_LOG_DIR", "disabled")
    subprocess.run([sys.executable, "-c", DUMP, tree, str(out),
                    "1" if tpu else "0"], check=True, env=env)


def main(argv) -> int:
    tpu = "--tpu" in argv
    old, new = [pathlib.Path(a).resolve() for a in argv if a != "--tpu"]
    with tempfile.TemporaryDirectory() as tmp:
        dirs = [pathlib.Path(tmp) / "old", pathlib.Path(tmp) / "new"]
        for tree, out in zip((old, new), dirs):
            out.mkdir()
            dump(str(tree), out, tpu)
        same = True
        for name in PROGRAMS:
            a, b = [(d / f"{name}.hlo").read_text() for d in dirs]
            counts = []
            for rename in (False, True):
                diff = [ln for ln in difflib.unified_diff(
                    strip(a, rename), strip(b, rename), lineterm="", n=0)
                    if ln[:1] in "+-" and ln[:3] not in ("+++", "---")]
                counts.append(len(diff))
            same &= counts[1] == 0
            print(json.dumps({"program": name, "lines": len(strip(b, True)),
                              "differ_metadata_stripped": counts[0],
                              "differ_also_renamed": counts[1]}))
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
