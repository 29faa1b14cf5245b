"""Operation counts of ``bench/costs.py`` against brute force and the hand
figures of the configurations."""
import numpy as np
import pytest

from bench import costs
from bench.run import load_config


@pytest.mark.parametrize("start,n", [(0, 7), (5, 3), (512, 512), (1536, 256)])
def test_chunk_pairs_counts_the_visible_keys(start, n):
    q = np.arange(start, start + n)[:, None]
    k = np.arange(start + n)[None, :]
    assert costs.chunk_pairs(start, n) == int((k <= q).sum())


@pytest.mark.parametrize("start,n,window", [
    (0, 7, 3), (5, 3, 1), (5, 3, 8), (0, 1, 1), (512, 512, 1024),
    (1536, 256, 1024), (1000, 48, 1024), (100, 50, 4096)])
def test_windowed_chunk_counts_the_keys_in_the_window(start, n, window):
    """The causal mask above, cut to the last ``window`` keys a query
    sees: the pairs, and the keys the chunk reads between its queries."""
    q = np.arange(start, start + n)[:, None]
    k = np.arange(start + n)[None, :]
    seen = (k <= q) & (k > q - window)
    assert costs.chunk_pairs(start, n, window) == int(seen.sum())
    assert costs.keys_read(start, n, window) == int(seen.any(0).sum())


@pytest.mark.parametrize("window", [None, 1, 16, 1024])
def test_a_decode_step_sees_the_lesser_of_its_position_and_window(window):
    for p in (0, 1, 15, 16, 1023, 1024, 5000):
        want = p + 1 if window is None else min(p + 1, window)
        assert costs.chunk_pairs(p, 1, window) == want
        assert costs.keys_read(p, 1, window) == want


@pytest.mark.parametrize("seq,chunk", [(1792, 512), (100, 32), (64, 64)])
def test_attention_work_is_the_same_however_it_is_chunked(seq, chunk):
    whole = costs.attention_flops(16, 128, costs.causal_pairs(seq))
    parts = sum(costs.attention_flops(16, 128, costs.chunk_pairs(
        s, min(chunk, seq - s))) for s in range(0, seq, chunk))
    assert parts == whole


def test_fp2fx8_bytes_are_int8_raws_and_a_float32_scale():
    assert costs.kv_bytes_per_token(16, 128, "fp2fx8") == 2 * 16 * (128 + 4)
    assert costs.kv_bytes_per_token(16, 128, "bfloat16") == 2 * 16 * 128 * 2


@pytest.mark.parametrize("name,seq,gflop", [("bert-base", 512, 0.68),
                                            ("olmo-1b", 2048, 7.46)])
def test_train_flops_per_token_match_the_hand_figures(name, seq, gflop):
    m = load_config(name)
    assert costs.train_flops_per_token(m, seq) / 1e9 == pytest.approx(
        gflop, rel=0.01)


def test_bert_matmul_weights():
    assert costs.matmul_params(load_config("bert-base")) == 108_375_552


def test_decode_flops_add_up_over_positions():
    m = load_config("olmo-1b")
    one = costs.decode_flops(m, [1000])
    assert costs.decode_flops(m, [1000, 1000]) == 2 * one
    assert one == pytest.approx(2 * costs.matmul_params(m) + 16 * 4 * 16 * 128
                                * 1001)


def test_roofline_share_takes_the_larger_bound():
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline_share(197e12, 0.0, 1.0, peak) == pytest.approx(100)
    assert costs.roofline_share(1.0, 819e9, 2.0, peak) == pytest.approx(50)
    assert costs.roofline_share(0.0, 1.0, 1.0, peak) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        costs.peaks("cpu")
