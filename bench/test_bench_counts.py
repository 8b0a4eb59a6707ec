"""The FLOP and byte counts against hand counts at a tiny size, and
against the program's own parameter count at the real one."""
import pytest

from bench import counts as C
from bench import harness as H

# D=4, F=6, H=2, K=1, dh=2, V=10, L=3
TINY = {"hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "vocab_size": 10,
        "num_hidden_layers": 3}


def test_layer_weights_by_hand():
    # q 4*4, k 4*2, v 4*2, o 4*4, gate/up/down 3*4*6
    assert C.layer_matrix_params(TINY) == 16 + 8 + 8 + 16 + 72
    # per layer + norms (2*4 + 2*2), three layers, embedding 10*4, final 4
    assert C.weight_bytes(TINY) == 2 * (3 * (120 + 8 + 4) + 40 + 4)


def test_prefill_by_hand():
    # B=2, S=3: matmuls 2*2*3*3*120; attention 4*2*3*2*2*(1+2+3);
    # logits of the last token 2*2*4*10
    assert C.prefill_flops(TINY, 2, 3) == (4320 + 576 + 160)
    # K and V of 2*3 tokens: 2 * 3 layers * 1 head * 2 dims * 2 bytes each
    assert C.prefill_bytes(TINY, 2, 3) == C.weight_bytes(TINY) + 6 * 24


def test_decode_by_hand():
    # B=2 at p=4 attends over 5 positions
    assert C.decode_flops(TINY, 2, 4) == (2 * 2 * 3 * 120
                                          + 4 * 2 * 3 * 2 * 2 * 5
                                          + 2 * 2 * 4 * 10)
    assert C.decode_bytes(TINY, 2, 4) == C.weight_bytes(TINY) + 10 * 24


def test_roofline_takes_the_slower_bound():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert C.least_seconds(500, 20, pk) == 5.0
    assert C.least_seconds(100, 50, pk) == 5.0


def test_map_bytes_by_hand():
    assert C.lookup_bytes(10, 1.5) == 10 * (4 + 4 + 12 + 1 + 4 + 1 + 4)
    assert C.update_bytes(10, 1.5, fresh=2, ins_ok=3, del_ok=4) == (
        10 * (12 + 4 + 12 + 1 + 1) + 17 * 2 + 5 * 1 + 4)


def test_weights_agree_with_the_program_at_full_size():
    from repro.configs.registry import get_arch
    cfg = H.load_json(H.BENCH / "configs" / "qwen3-1.7b.json")
    arch = get_arch("qwen3-1.7b")
    norms = arch.n_layers * (2 * arch.d_model + 2 * arch.head_dim) \
        + arch.d_model
    # the program's count leaves out the qk-norm weights and the final norm
    assert C.weight_bytes(cfg) == 2 * (
        arch.n_params() - arch.n_layers * 2 * arch.d_model + norms)
    assert C.weight_bytes(cfg) == pytest.approx(3.44e9, rel=0.01)
