"""FLOP and byte counts from the configurations' declared shapes, against
hand counts; and the declared shapes against the program's parameters."""
import json
from pathlib import Path

import jax
import pytest

from fdbench import flops

HERE = Path(__file__).resolve().parent
CNN = json.loads((HERE / "configs" / "cnn_zoo_cifar10.json").read_text())
MLP = json.loads((HERE / "configs" / "mlp_feat512.json").read_text())
PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_one_table_ii_architecture_by_hand():
    # arch 3 of Table II: conv 64 5x5 SAME, pool, conv 128 5x5 SAME, pool,
    # linear 8*8*128 -> 256, linear 256 -> 10, on 32x32x3
    hand = (2 * 32 * 32 * 5 * 5 * 3 * 64
            + 2 * 16 * 16 * 5 * 5 * 64 * 128
            + 2 * (8 * 8 * 128) * 256
            + 2 * 256 * 10)
    assert flops.forward_flops(CNN, 2) == hand == 118_887_424
    assert flops.forward_flops(CNN, 12) == hand          # client 12: arch 3


def test_the_zoo_and_the_mlp():
    zoo = sum(flops.forward_flops(CNN, i) for i in range(10))
    assert zoo == 816_555_008                  # 0.817 GFLOP per sample
    assert flops.forward_flops(MLP, 0) == 2 * (512 * 256 + 256 * 128
                                               + 128 * 10)


def test_round_flops_by_hand():
    traffic = {"batch_size": 64, "samples_per_client": 500,
               "proxy_batch": 512, "n_test": 1000,
               "participation_fraction": 1.0}
    f = 2 * (512 * 256 + 256 * 128 + 128 * 10)
    # 7 full batches of 64 train, 512 proxy rows report and distill,
    # 1000 test rows, and the filter's 512 x 10 distances over 512 dims
    per_client = (3 * f * 448 + f * 512 + 2 * 512 * 10 * 512
                  + 3 * f * 512 + f * 1000)
    assert flops.round_flops(MLP, traffic, [100], 10) == 100 * per_client


@pytest.mark.parametrize("arch", range(10))
def test_declared_shapes_match_the_program(arch):
    from repro.models.cnn import get_client_model

    spec, hw, ch = get_client_model(arch, "cifar10")
    got = jax.eval_shape(lambda k: spec.init(k, hw, ch),
                         jax.random.PRNGKey(0))
    assert [{k: tuple(v.shape) for k, v in layer.items()}
            for layer in got] == flops.param_shapes(CNN, arch)


def test_declared_mlp_shapes_match_the_program():
    from repro.models.cnn import MLPClassifier

    mlp = MLPClassifier(512, tuple(MLP["hidden"]), 10)
    got = jax.eval_shape(mlp.init, jax.random.PRNGKey(0))
    assert [{k: tuple(v.shape) for k, v in layer.items()}
            for layer in got] == flops.param_shapes(MLP, 0)


def test_distill_kl_cost_and_roofline():
    n, k = 64, 10
    assert flops.distill_kl_cost(n, k, "fwd") == (64 * 134, 64 * 84)
    assert flops.distill_kl_cost(n, k, "bwd_ds") == (64 * 154, 64 * 124)
    t, bound = flops.roofline_seconds(*flops.distill_kl_cost(n, k, "fwd"),
                                      PEAKS)
    assert bound == "memory" and t == pytest.approx(64 * 84 / 819e9)
    t, bound = flops.roofline_seconds(4e12, 1.0, PEAKS)
    assert bound == "compute" and t == pytest.approx(4e12 / 197e12)
    with pytest.raises(ValueError):
        flops.distill_kl_cost(n, k, "bwd_dx")
