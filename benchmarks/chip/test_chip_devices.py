"""The reference over the cell's chips, on four CPU devices: it reads on four
what it reads on one; client ``cid``'s state, private data and filter lie
on ``devices[cid % 4]``, where its calls run; each update donates the state
it replaces. And ``mfu`` is read against all the cell's chips."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from fdbench import harness

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
CELL = "mlp_feat512.c100_iid"
SMALL = {"num_clients": 4, "samples_per_client": 200, "proxy_batch": 128,
         "n_test": 100}
SEED = 2 ** 31 + 17
PEAKS = harness.load_json(HERE / "peaks.json")["devices"]["TPU v5 lite"]

# follows the cell's first rounds at SMALL on one device, then on four
# with the jitted calls watched
PROG = f'''
import json, sys
sys.path.insert(0, {str(HERE)!r})
import jax
from fdbench import harness, reference

cell = harness.load_cell(harness.HERE.parents[1], {CELL!r})
traffic = dict(cell.traffic, **{SMALL!r})
data = harness.make_data(cell.config, traffic, {SEED})
devs = jax.devices()
one = reference.run(cell.config, traffic, data, {SEED}, devices=devs[:1])


def where(*trees):
    return sorted({{devs.index(d) for a in jax.tree_util.tree_leaves(trees)
                   for d in a.devices()}})


calls = {{"train": [], "distill": [], "filter": []}}
make = reference._make_fns


def watched(*a, **k):
    train, distill, logits, correct = make(*a, **k)

    def watch(name, fn):
        def call(params, mu, *rest):
            state = jax.tree_util.tree_leaves((params, mu))
            # the last input, the step plan, is the host's
            seen = {{"state": where(params, mu), "inputs": where(rest[:-1])}}
            out = fn(params, mu, *rest)
            seen["donated"] = all(a.is_deleted() for a in state)
            calls[name].append(seen)
            return out
        return call

    return watch("train", train), watch("distill", distill), logits, correct


mask = reference._filter_mask


def watched_mask(cents, thr, px_flat, powner, cid):
    calls["filter"].append({{"cid": cid, "filter": where(cents, thr)}})
    return mask(cents, thr, px_flat, powner, cid)


reference._make_fns = watched
reference._filter_mask = watched_mask
four = reference.run(cell.config, traffic, data, {SEED}, devices=devs)
print(json.dumps({{"devices": len(devs), "one": one, "four": four,
                  "calls": calls}}))
'''


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["devices"] == 4
    return res


def test_readings_on_four_devices_are_those_on_one(runs):
    assert runs["four"] == runs["one"]


def test_each_client_lives_on_its_device(runs):
    n = SMALL["num_clients"]
    for name in ("train", "distill"):
        seen = runs["calls"][name]
        assert len(seen) == 3 * n           # three rounds, clients in order
        for k, call in enumerate(seen):
            assert call["state"] == call["inputs"] == [k % n % 4], (name, k)
    filters = runs["calls"]["filter"]
    assert [c["cid"] for c in filters] == list(range(n)) * 3
    assert all(c["filter"] == [c["cid"] % 4] for c in filters)


def test_each_update_donates_the_state(runs):
    for name in ("train", "distill"):
        assert all(c["donated"] for c in runs["calls"][name]), name


def test_mfu_reads_against_all_the_cells_chips():
    read = harness.load_reader("mfu")
    ctx = SimpleNamespace(round_flops=1.45566e11, round_s=0.0335,
                          peaks=PEAKS, chips=1)
    one = read(ctx)
    assert one == pytest.approx(100 * 1.45566e11 / (0.0335 * 197e12))
    ctx.chips = 4
    assert read(ctx) == pytest.approx(one / 4, rel=1e-12)
