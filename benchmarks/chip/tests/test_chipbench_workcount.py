"""Operation and byte counts from shapes, against counts made by hand."""
import json

import pytest

import workcount
from conftest import CHIP

CFG = json.loads((CHIP / "configs" / "b_alexnet.json").read_text())


def test_edge_partition_at_branch_1():
    # conv1: 32*32*64 outputs * 5*5*3 MACs; branch 1: 16*16*32 * 3*3*64
    # MACs and a 2048 x 10 head; two operations per MAC
    hand = 2 * (32 * 32 * 64 * 75 + 16 * 16 * 32 * 576 + 2048 * 10)
    assert workcount.edge_flops(CFG, 1) == hand
    assert workcount.edge_flops(CFG, 1) / 1e6 == pytest.approx(19.3, abs=0.05)


def test_cloud_partition_from_branch_1():
    hand = 2 * (16 * 16 * 96 * 1600 + 8 * 8 * 192 * 864 + 8 * 8 * 128 * 1728
                + 8 * 8 * 128 * 1152 + 2048 * 256 + 256 * 128 + 128 * 10)
    assert workcount.cloud_flops(CFG, 1) == hand
    assert workcount.cloud_flops(CFG, 1) / 1e6 == pytest.approx(148.1, abs=0.1)


def test_branch_2_partitions_cover_the_trunk():
    trunk = workcount.edge_flops(CFG, 1) - workcount.branch_flops(CFG, 1) \
        + workcount.cloud_flops(CFG, 1)
    assert workcount.edge_flops(CFG, 2) - workcount.branch_flops(CFG, 2) \
        + workcount.cloud_flops(CFG, 2) == trunk


def test_codec_bytes_for_205_payloads():
    n = workcount.payload_elements(CFG, 1)
    assert n == 16 * 16 * 64
    # per row: 65,536 B of float32 payload, 16,384 B of int8 words and
    # 128 float32 scales; read and written once by encode and by decode
    per_row = 2 * (4 * 16384 + 16384 + 4 * 128)
    assert workcount.codec_bytes(205, n, 8, 128) == 205 * per_row == 33_797_120
    assert workcount.codec_bytes(205, n, 4, 128) == 205 * 2 * (4 * 16384 + 8192 + 512)


def test_gate_bytes_leave_out_the_padded_tile():
    assert workcount.gate_bytes(256, 10) == 256 * (40 + 12)
