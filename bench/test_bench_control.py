"""The controls of ``correct`` at a size a CPU test holds: each must fail
the comparison the program passes."""
import jax
import pytest

from bench import control, tiny


def test_fp8_control_reads_a_wider_gap_than_the_served_tokens():
    """The program serves bf16; the reference with float8 products puts
    other tokens first, its gap is several times the program's, and in the
    run's own checks it turns ``correct`` false."""
    cell = tiny.tiny_cell("serve-qwen3-1.7b-over")
    res = control.serve_control(cell, 3, jax.devices()[:1], 1.0)
    assert res["correct"]
    assert res["control_flips"] > 0
    assert res["control_logit_gap"] > 3 * res["program_logit_gap"]
    assert not res["control_correct"]


@pytest.mark.parametrize("name", ["hash-50u", "hash-read"])
def test_map_control_breaks_durability_and_is_caught(name):
    cell = tiny.map_cell(tiny.MAP_MIXES[name])
    whole = control.map_control(cell, 1, jax.devices()[:1], 0.3, False)
    lost = control.map_control(cell, 1, jax.devices()[:1], 0.3, True)
    assert whole["correct"]
    assert not lost["correct"]
    assert lost["lookup_mismatch"] > 0 and lost["content_mismatch"] > 0
