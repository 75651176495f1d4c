"""Model families are found by the name a configuration gives: a new family
is a new file, and the llama family gives, number for number, what the
harness gave before the family was moved into ``bench/families/``."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchcells import tiny_cell
from harness import cells, flops
from harness.reference import HP

ROOT = Path(__file__).resolve().parents[2]
BEFORE = json.loads((Path(__file__).parent / "data" / "llama_before.json")
                    .read_text())
CONFIGS = sorted(BEFORE["configs"])

TOY = '''"""A family that counts a fixed number of matmul parameters."""


def matmul_params(c):
    return c["toy_params"]


def attention_flops(c, context):
    return 0.0
'''

PROBE = '''
import json, sys, types
sys.path.insert(0, "bench")
from harness import cells, flops
cell = cells.resolve("train.toy.s2048")
fam = cells.family(cell)
run = types.SimpleNamespace(kind="train", cell=cell, tokens=4 * 2048,
                            window_s=2.0, peaks={"bf16_flops": 1e6})
print(json.dumps({"file": fam.__file__,
                  "mfu": cells.metric_reader("mfu.train")(run)}))
'''


def test_a_new_family_is_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "families" / "toy.py").write_text(TOY)
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "nowhere", "family": "toy",
         "toy_params": 1000, "reduced": []}))
    (tmp_path / "bench" / "limits" / "train.toy.s2048.json").write_text(
        (ROOT / "bench" / "limits" / "train.smollm-360m.s2048.json").read_text())
    bench["configs"].append({"name": "toy", "source": "nowhere",
                             "file": "bench/configs/toy.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "train.toy.s2048", "config": "toy",
                               "traffic": "train-s2048", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train.smollm-360m.s2048" in m.get("workloads", ()):
            m["workloads"].append("train.toy.s2048")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(out["file"]) == tmp_path / "bench" / "families" / "toy.py"
    # 4 rows of 3 x 2048 x 2 x 1000 FLOPs over 2 s at 1e6 FLOP/s
    assert out["mfu"] == pytest.approx(100.0 * 4 * 3 * 2048 * 2000 / 2e6)


def _llama(name):
    c = cells.load_json(cells.BENCH / "configs" / f"{name}.json")
    return c, cells.family(cells.Cell(name=name, config=c, traffic={},
                                      chips=1, end_to_end=[], per_layer=[]))


@pytest.mark.parametrize("name", CONFIGS)
def test_llama_program_config_as_before(name):
    c, fam = _llama(name)
    cfg = fam.program_config(c, use_pallas=False, remat="full")
    got = {k: repr(v) for k, v in dataclasses.asdict(cfg).items()}
    assert got == BEFORE["configs"][name]["program_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_llama_flop_counts_as_before(name):
    c, fam = _llama(name)
    want = BEFORE["configs"][name]
    assert fam.matmul_params(c) == want["matmul_params"]
    assert fam.attention_flops(c, 2048).hex() == want["attention_flops_2048"]
    assert (flops.train_flops_per_sequence(fam, c, 2048).hex()
            == want["train_flops_per_sequence_2048"])
    assert (flops.serve_flops(fam, c, [300, 17], [64, 5]).hex()
            == want["serve_flops"])


@pytest.mark.parametrize("name", CONFIGS)
def test_llama_reference_loss_as_before(name):
    import jax

    c, fam = _llama(name)
    small = dict(c, num_hidden_layers=2)
    s = fam.Spec.from_config(small)
    m = small["mup"]
    hp = HP(lr=1e-3, sigma=m["sigma"], alpha_output=m["alpha_output"],
            alpha_attn=m["alpha_attn"], alpha_embed=m["alpha_embed"])
    theta = fam.init(jax.random.PRNGKey(7), s, hp.sigma)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 17), 0, s.vocab)
    loss = jax.jit(lambda th, t: fam.loss(th, t[:, :-1], t[:, 1:], s, hp,
                                          chunk=8))(theta, toks)
    assert float(loss).hex() == BEFORE["configs"][name]["reference_loss_2_layers"]


def test_one_device_reference_steps_as_before():
    import jax

    from harness.run_train import reference_steps

    cell = tiny_cell("train.smollm-360m.s2048")
    rows = np.random.default_rng(3).integers(0, 256, size=(3, 8, 65))
    losses, g0, dn = reference_steps(cell, 2**31 + 77, rows.astype(np.int32),
                                     jax.devices()[:1])
    want = BEFORE["reference_steps_tiny"]
    assert [float(x).hex() for x in losses] == want["losses"]
    assert [float(x).hex() for x in g0] == want["grad_norms"]
    assert [float(x).hex() for x in dn] == want["change_norms"]
