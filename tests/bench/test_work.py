"""Operations and bytes per kernel call against counts made by hand: the
calls of :mod:`bench.work`, and the dense kind's tick (a file that names
no kind is dense)."""
import pytest

from bench import spec, work

MISTRAL = {"hidden_size": 5120, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "vocab_size": 131072, "num_hidden_layers": 5}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DENSE = spec.model_kind(MISTRAL)


def test_gemm_by_hand():
    # A decode tick's MLP-in GEMM of stablelm: 16 rows, K 2048, N 5632.
    flops, nbytes = work.gemm(16, 2048, 5632)
    assert flops == 2 * 16 * 2048 * 5632 == 369_098_752
    # bf16 A (16 x 2048) + B (2048 x 5632) + C (16 x 5632)
    assert nbytes == 2 * (32_768 + 11_534_336 + 90_112) == 23_314_432
    assert work.least_time(flops, nbytes, PEAKS) == pytest.approx(
        23_314_432 / 819e9)


def test_decode_attention_reads_live_kv_only():
    # Two rows live at 100 and 37 positions in a table of 4608: the work
    # is the live positions', not the table's.
    flops, nbytes = work.decode_attention([100, 37], heads=32, kv_heads=8,
                                          head_dim=128)
    assert flops == 4 * 32 * 128 * 137 == 2_244_608
    kv = 2 * 8 * 128 * 137 * 2            # K and V, bf16
    q_out = 2 * 32 * 128 * 2 * 2          # q in and out per row, bf16
    assert nbytes == kv + q_out == 593_920


def test_tick_flops_by_hand():
    # A prefill chunk of 3 tokens at positions 5-7 (contexts 6, 7, 8),
    # logits at its last.
    layers, params = 5, DENSE.layer_params(MISTRAL)
    dense = 3 * 2.0 * layers * params
    attn = 4.0 * layers * 32 * 128 * (6 + 7 + 8)
    head = 2.0 * 5120 * 131072
    assert DENSE.tick_flops(MISTRAL, [(5, 3)], 1) == pytest.approx(
        dense + attn + head)


def test_layer_gemms_cover_the_layer():
    d, ff, q, kv = 5120, 14336, 32 * 128, 8 * 128
    assert DENSE.layer_params(MISTRAL) == (d * q + 2 * d * kv + q * d
                                           + 3 * d * ff)
