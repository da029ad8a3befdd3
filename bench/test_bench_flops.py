"""Work counted from shapes, against hand counts, and the peaks table."""
import pytest

from bench import flops

PAPER_CNN = dict(input_shape=[32, 32, 3], channels=[32, 32], hidden=[128],
                 n_classes=10)


def test_cnn_flops_per_sample_hand_count():
    # forward MACs: conv0 32*32*32*27, conv1 16*16*32*288, fc0 2048*128,
    # head 128*10; 2 FLOPs a MAC forward, 2 for weight gradients, 2 for
    # input gradients of every layer but the first
    macs = [884_736, 2_359_296, 262_144, 1_280]
    assert flops.cnn_layer_macs(PAPER_CNN) == macs
    assert flops.cnn_train_flops_per_sample(PAPER_CNN) == \
        6 * sum(macs) - 2 * macs[0] == 19_275_264


def test_cnn_param_count():
    assert flops.cnn_param_count(PAPER_CNN) == 273_706


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
