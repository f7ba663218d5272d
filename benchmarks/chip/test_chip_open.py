"""What a later change adds by new files alone: a model kind
(``models/<kind>.py``), a configuration, a traffic file whose keys are
``FedConfig`` fields, a limits file and a ``BENCHMARK.json`` entry; and
the checks that keep such a cell honest (unknown traffic keys and model
kinds are refused, and so is a cell whose program spans fewer devices
than it asks for)."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from fdbench import flops, harness, kinds, reference

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
C100 = harness.load_json(HERE / "traffic" / "c100_iid.json")
SEED = 2 ** 31 + 11
SMALL = {"num_clients": 4, "samples_per_client": 200, "proxy_batch": 128,
         "n_test": 100}
LIMITS = {"limits": {"loss_gap": 1e-3, "grad_gap": 1e-3, "update_gap": 1e-3,
                     "acc_gap": 0.021, "id_gap": 0.08}, "not_compared": {}}

# a one-hidden-layer client MLP, written out without fdbench.layers, with
# data of its own
TOY_MODEL = '''"""One hidden layer of ReLU units between features and classes."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from fdbench import fleetdata


def _dims(config):
    return (config["input"]["feature_dim"], config["hidden"],
            config["num_classes"])


def param_shapes(config, cid):
    d, h, k = _dims(config)
    return [{"b": (h,), "w": (d, h)}, {"b": (k,), "w": (h, k)}]


def forward_flops(config, cid):
    d, h, k = _dims(config)
    return 2 * (d * h + h * k)


def filter_dim(config):
    return config["input"]["feature_dim"]


def init_params(key, config, cid):
    dims = _dims(config)
    params = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        params.append({"w": jax.random.normal(sub, (d_in, d_out))
                       / math.sqrt(d_in), "b": jnp.zeros((d_out,))})
    return params


def make_apply(config, cid, precision):
    def apply(params, x, train):
        first, last = params
        h = jax.nn.relu(jnp.dot(x, first["w"], precision=precision)
                        + first["b"])
        return jnp.dot(h, last["w"], precision=precision) + last["b"]
    return apply


def arch_key(config, cid):
    return "mlp1"


def build_kwargs(config):
    return {"mlp_hidden": (config["hidden"],)}


def make_dataset(spec, n_train, n_test, seed):
    k, d = spec["num_classes"], spec["feature_dim"]
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(k, d)) * spec["separation"]

    def draw(n):
        y = rng.permutation(np.arange(n) % k).astype(np.int32)
        return (means[y] + rng.normal(size=(n, d))).astype(np.float32), y

    x, y = draw(n_train)
    xt, yt = draw(n_test)
    return fleetdata.Dataset(x, y, xt, yt, k, spec["name"])
'''
TOY_CONFIG = {
    "name": "toy_feat64", "source": "a test's own", "model": "mlp1",
    "dtype": "float32", "matmul_precision": "default",
    "input": {"feature_dim": 64}, "num_classes": 4, "hidden": 32,
    "dataset": {"name": "toy_feat64", "num_classes": 4, "feature_dim": 64,
                "separation": 1.5}}
TINY = dict(C100, **SMALL)

# runs cells of the benchmark tree at argv[1], as it stands there
PROG = f'''
import json, sys
from pathlib import Path
root = Path(sys.argv[1])
sys.path.insert(0, str(root / "benchmarks" / "chip"))
from fdbench import harness
assert harness.HERE == root / "benchmarks" / "chip", harness.HERE
harness.enable_cache = lambda: None
for cell in sys.argv[2:]:
    try:
        res = harness.run(cell, {SEED}, 0.2, False, root=root, t_process=0.0,
                          require_tpu=False, log=lambda *a, **k: None)
    except harness.Refused as e:
        res = {{"refused": str(e)}}
    print(json.dumps({{cell: res}}), flush=True)
'''


class Tree:
    """A copy of the benchmark under ``root``, to which files are added."""

    def __init__(self, root: Path):
        self.root = root
        shutil.copytree(HERE, root / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      "testdata"))
        shutil.copy(ROOT / "BENCHMARK.json", root)
        self.bench = json.loads((root / "BENCHMARK.json").read_text())

    def add(self, rel: str, content) -> None:
        path = self.root / "benchmarks" / "chip" / rel
        assert not path.exists(), f"{rel} is already there"
        path.write_text(content if isinstance(content, str)
                        else json.dumps(content))

    def add_cell(self, name: str, config: str, traffic: str,
                 chips: int = 1) -> None:
        self.bench["workloads"].append(
            {"name": name, "config": config, "traffic": traffic,
             "chips": chips, "why": "a test's own"})
        self.add(f"limits/{name}.json", LIMITS)

    def run(self, *cells, devices: int = 1) -> dict:
        (self.root / "BENCHMARK.json").write_text(json.dumps(self.bench))
        env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
                   JAX_PLATFORMS="cpu")
        if devices > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                f"host_platform_device_count={devices}")
        out = subprocess.run([sys.executable, "-c", PROG, str(self.root),
                              *cells], env=env, capture_output=True,
                             text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        res = {}
        for line in out.stdout.splitlines():
            res.update(json.loads(line))
        return res

    def unchanged(self) -> bool:
        """Every file of the benchmark that was there is as it was."""
        for src in HERE.rglob("*"):
            rel = src.relative_to(HERE)
            if src.is_file() and not {"__pycache__", "testdata"} \
                    & set(rel.parts):
                copy = self.root / "benchmarks" / "chip" / rel
                if copy.read_bytes() != src.read_bytes():
                    return False
        return True


# ------------------------------------------------------------ new files alone
def test_a_model_kind_added_by_new_files_alone(tmp_path):
    tree = Tree(tmp_path)
    tree.add("models/mlp1.py", TOY_MODEL)
    tree.add("configs/toy_feat64.json", TOY_CONFIG)
    tree.add("traffic/c4_tiny.json", TINY)
    tree.bench["configs"].append(
        {"name": "toy_feat64", "source": "a test's own",
         "file": "benchmarks/chip/configs/toy_feat64.json", "reduced": [],
         "why": "a test's own"})
    tree.add_cell("toy_feat64.c4_tiny", "toy_feat64", "c4_tiny")
    res = tree.run("toy_feat64.c4_tiny")["toy_feat64.c4_tiny"]
    assert res.get("correct"), res
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "round_s", "peak_hbm_gib"}
    assert tree.unchanged()


def test_a_cell_spanning_fewer_devices_than_its_chips_is_refused(tmp_path):
    tree = Tree(tmp_path)
    tree.add("traffic/c4_tiny.json", TINY)
    tree.add("traffic/c4_tiny_mesh.json", dict(TINY, num_devices=4))
    tree.add_cell("mlp_feat512.c4_tiny", "mlp_feat512", "c4_tiny", chips=4)
    tree.add_cell("mlp_feat512.c4_tiny_mesh", "mlp_feat512", "c4_tiny_mesh",
                  chips=4)
    res = tree.run("mlp_feat512.c4_tiny", "mlp_feat512.c4_tiny_mesh",
                   devices=4)
    assert "span 1 device" in res["mlp_feat512.c4_tiny"]["refused"]
    mesh = res["mlp_feat512.c4_tiny_mesh"]
    assert "refused" not in mesh and mesh["correct"], mesh
    assert tree.unchanged()


# --------------------------------------------------------------- the traffic
def test_traffic_keys_reach_the_program():
    cfg = harness.fed_config(dict(C100, staleness_decay=0.5, model_shards=2),
                             SEED)
    assert cfg.staleness_decay == 0.5 and cfg.model_shards == 2
    assert cfg.seed == SEED


@pytest.mark.parametrize("key", ["staleness_decy", "seed"])
def test_a_traffic_key_the_program_lacks_is_refused(key):
    with pytest.raises(harness.Refused, match=key):
        harness.fed_config(dict(C100, **{key: 1}), SEED)


def test_c100_iid_gives_the_fedconfig_it_gave_before():
    from repro.common.types import FedConfig

    t = C100
    before = FedConfig(
        num_clients=t["num_clients"], method=t["method"],
        scenario=t["scenario"], local_epochs=t["local_epochs"],
        distill_epochs=t["distill_epochs"],
        proxy_fraction=t["proxy_fraction"], proxy_batch=t["proxy_batch"],
        batch_size=t["batch_size"], lr=t["lr"], temperature=t["temperature"],
        participation_fraction=t["participation_fraction"],
        engine=t["engine"], round_mode=t["round_mode"], seed=SEED)
    assert dataclasses.asdict(harness.fed_config(C100, SEED)) \
        == dataclasses.asdict(before)


# --------------------------------------------------------------- model kinds
@pytest.mark.parametrize("call", [
    lambda c: flops.param_shapes(c, 0),
    lambda c: flops.forward_flops(c, 0),
    lambda c: reference.init_params(jax.random.PRNGKey(0), c, 0)])
def test_an_unknown_model_kind_is_refused(call):
    with pytest.raises(harness.Refused, match="models/no_such_kind.py"):
        call({"model": "no_such_kind"})


def test_a_model_module_lacking_a_function_is_refused(tmp_path, monkeypatch):
    (tmp_path / "models").mkdir()
    (tmp_path / "models" / "half_kind.py").write_text(
        "def param_shapes(config, cid):\n    return []\n")
    monkeypatch.setattr(kinds, "HERE", tmp_path)
    monkeypatch.delitem(sys.modules, "fdbench_model_half_kind", raising=False)
    with pytest.raises(harness.Refused, match="lacks forward_flops"):
        kinds.load({"model": "half_kind"})
    sys.modules.pop("fdbench_model_half_kind", None)


# ------------------------------------------------------------------- leaves
def test_leaves_in_layer_order_with_keys_sorted():
    # the harness slices the program's stacked leaves and the reference
    # flattens its own with jax.tree_util; for the list of per-layer dicts
    # both sides hold, that order is layer by layer with the keys sorted
    def layer(i):
        return {k: jnp.full((2, 3), 10 * i + j, jnp.float32)
                for j, k in enumerate(("w", "b", "scale"))}

    stacked = [layer(0), layer(1)]
    order = [lyr[k] for lyr in stacked for k in sorted(lyr)]
    assert reference._leaves(stacked) == order
    engine = SimpleNamespace(cohorts=[SimpleNamespace(
        positions=[7, 3], params=stacked)])
    got = dict(harness.member_leaves(engine, lambda c: c.params))
    assert sorted(got) == [3, 7]
    for j, cid in enumerate([7, 3]):
        assert [a.tolist() for a in got[cid]] \
            == [a[j].tolist() for a in order]
