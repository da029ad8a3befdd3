"""Work counted from shapes, against hand counts, the CNN model module's
counts and weights, and the peaks table."""
import hashlib

import numpy as np
import pytest

from bench import flops, traffic
from bench.models import cnn

PAPER_CNN = dict(input_shape=[32, 32, 3], channels=[32, 32], hidden=[128],
                 n_classes=10)


def test_cnn_flops_per_sample_hand_count():
    # forward MACs: conv0 32*32*32*27, conv1 16*16*32*288, fc0 2048*128,
    # head 128*10; 2 FLOPs a MAC forward, 2 for weight gradients, 2 for
    # input gradients of every layer but the first
    macs = [884_736, 2_359_296, 262_144, 1_280]
    assert cnn.layer_macs(PAPER_CNN) == macs
    assert cnn.train_flops_per_sample(PAPER_CNN) == \
        6 * sum(macs) - 2 * macs[0] == 19_275_264


def test_cnn_param_count():
    assert cnn.param_count(PAPER_CNN) == 273_706


def test_cnn_weights_keep_their_bytes():
    """The weights of a seed, leaf paths and bytes, as the benchmark made
    them before the CNN became a model module."""
    import jax

    params, frozen = cnn.split(
        cnn.init(traffic.seed_key(2**33 + 17, 3), PAPER_CNN), PAPER_CNN)
    assert frozen == {}
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)) == \
        cnn.param_count(PAPER_CNN)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "6e13e7cfb1e6c9543e9479945b088c0df1b7344e279bd169f52fb72c1b22e392"


@pytest.mark.parametrize("rows,n,expected", [
    (100, 273_706, 221_154_448),     # 4 * (2*100*N + 2*N)
    (256, 273_706, 4 * (2 * 256 * 273_706 + 2 * 273_706)),
    (1, 1, 16)])
def test_echo_kernel_bytes(rows, n, expected):
    assert flops.echo_kernel_bytes(rows, n) == expected


def test_peaks_known_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_peaks_refuse_unknown_kind(kind):
    with pytest.raises(ValueError, match="no peaks"):
        flops.peaks(kind)
