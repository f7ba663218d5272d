"""The cohort's batch plan (``_Cohort._plan``) packs clients by size.

Members of one private-set size share ``w`` and ``valid`` and differ only
in their permutations, so ``_plan`` shuffles each size group into one
buffer and cuts ``idx`` from it in one reshape. It must give the plan the
per-client loop gave, bit for bit, and leave every client's generator
where ``epochs`` calls of ``permutation(n)`` leave it: the loop engine
draws those, and the loop == cohort parity tests rest on the streams
staying in step.
"""
import types

import numpy as np
import pytest

from repro.common import tracing
from repro.data.partition import partition
from repro.fed.batching import padded_epoch_plan, steps_per_epoch
from repro.fed.cohort import _Cohort

B = 32


def _loop_plan(members, ns, lead, epochs, batch_size, weight=None,
               part=None):
    """The per-client plan loop ``_plan`` replaced, kept as the reference."""
    steps = max(steps_per_epoch(n, batch_size) for n in ns) * epochs
    idx = np.zeros((lead, steps, batch_size), np.int32)
    w = np.zeros((lead, steps, batch_size), np.float32)
    valid = np.zeros((lead, steps), bool)
    for i, c in enumerate(members):
        if part is not None and not part[i]:
            continue
        perms = [c.rng.permutation(ns[i]) for _ in range(epochs)]
        idx[i], w[i], valid[i] = padded_epoch_plan(perms, batch_size, steps)
    if weight is not None:
        w = w * np.asarray(weight, np.float32)[idx]
    return idx, w, valid


def _strong_sizes():
    y = np.random.default_rng(3).integers(0, 10, 700)
    parts = partition(np.zeros((len(y), 1)), y, num_clients=4,
                      num_classes=10, scenario="strong", seed=1)
    return [len(p.y) for p in parts]


def _cohort(sizes, *, waved=False, c_pad=None, seed=0):
    """The attributes ``_plan`` reads, with one generator per member."""
    members = [types.SimpleNamespace(rng=np.random.default_rng(seed + i))
               for i in range(len(sizes))]
    return types.SimpleNamespace(
        members=members, n=np.array(sizes, np.int64), _waved=waved,
        c_pad=len(sizes) if c_pad is None else c_pad)


CASES = {
    # name: (private sizes, draw_n (-1: private), epochs, part, layout)
    "equal": ([500] * 6, -1, 1, None, {}),
    "equal_2_epochs": ([500] * 6, -1, 2, None, {}),
    "strong": (_strong_sizes(), -1, 1, None, {}),
    "strong_2_epochs": (_strong_sizes(), -1, 2, None, {}),
    "mixed_repeats": ([200, 200, 130, 200, 130, 77], -1, 2, None, {}),
    "short_and_empty": ([10, 64, 0, 10, 130, 0], -1, 2, None, {}),
    "part": ([200, 200, 130, 200, 130, 77], -1, 2,
             [True, False, True, True, False, False], {}),
    "waved": ([200, 200, 130, 77, 200], -1, 1, None,
              {"waved": True, "c_pad": 2}),
    "mesh_padded": ([200, 130, 200, 77, 130], -1, 2, None, {"c_pad": 8}),
    "distill_weight": ([500] * 5, 300, 1, None, {"c_pad": 8}),
    "distill_weight_part": ([500] * 5, 300, 2,
                            [False, True, True, False, True], {}),
    "distill_short": ([500] * 3, 20, 2, None, {}),
    "distill_empty": ([500] * 3, 0, 1, None, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_plan_matches_per_client_loop(case):
    sizes, draw_n, epochs, part, layout = CASES[case]
    weight = None
    if draw_n > 0:
        weight = np.random.default_rng(7).random(draw_n).astype(np.float32)
    got, ref = _cohort(sizes, **layout), _cohort(sizes, **layout)
    lead = len(sizes) if layout.get("waved") else ref.c_pad
    ns = [draw_n] * len(sizes) if draw_n >= 0 else sizes
    want = _loop_plan(ref.members, ns, lead, epochs, B, weight, part)
    plan = _Cohort._plan(got, draw_n, epochs, B, weight=weight, part=part)
    for name, a, b in zip(("idx", "w", "valid"), plan, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    for i, (g, r) in enumerate(zip(got.members, ref.members)):
        assert g.rng.bit_generator.state == r.rng.bit_generator.state, i
    if part is not None:                   # sampled-out: nothing drawn
        fresh = _cohort(sizes, **layout).members
        for i in np.flatnonzero(~np.asarray(part)):
            assert (got.members[i].rng.bit_generator.state
                    == fresh[i].rng.bit_generator.state), i


@pytest.mark.parametrize("case", ["equal", "strong", "short_and_empty",
                                  "part", "distill_weight_part"])
def test_plan_groups_counts_distinct_drawing_sizes(case):
    sizes, draw_n, epochs, part, layout = CASES[case]
    drawing = [i for i in range(len(sizes)) if part is None or part[i]]
    ns = [draw_n] * len(sizes) if draw_n >= 0 else sizes
    at = tracing.counts()
    _Cohort._plan(_cohort(sizes, **layout), draw_n, epochs, B, part=part)
    booked = {}
    tracing.book(booked, at)
    assert booked["engine.plan_groups"] == len({ns[i] for i in drawing})
