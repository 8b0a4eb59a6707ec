"""Cells of BENCHMARK.json cut to a size a CPU test can hold.

The drivers, traffic and references are the benchmark's own; only the
sizes shrink.  Used by the CPU rehearsals under ``bench/`` and by the
control's test.
"""
from __future__ import annotations

import copy

from bench import harness as H

TINY_MODEL = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=512, initializer_range=0.125)
# the tiny model on a CPU serves some 200 requests a second: the tiny mix
# offers more, so that a cell above the knee stays above it
TINY_CHAT = dict(rate_per_s=400.0, lengths=[8, 16, 32], new_tokens=8)
# the tiny model's logits spread less than the full model's: its limit is
# set from its own readings (the program about 0.002, the float8 control
# about 0.3)
TINY_CHECKS = {"logit_gap": 0.05}
# No configuration of BENCHMARK.json runs the map drivers yet: their
# rehearsals run a map deployment of this size, with the mixes of
# bench/traffic/ that the names below stand for.
TINY_MAP = dict(nodes=4096, buckets=1024, key_range=3000, prefill=1500,
                round_ops=512)
MAP_MIXES = {"hash-50u": "50u", "hash-read": "read"}


def tiny_cell(name: str) -> H.Cell:
    """The named cell of BENCHMARK.json at tiny size."""
    cell = copy.deepcopy(H.find_cell(name))
    if cell.config["driver"] == "serve":
        cell.config.update(TINY_MODEL, checks=TINY_CHECKS)
        cell.traffic.update(TINY_CHAT)
    return cell


def map_cell(traffic: str, chips: int = 1) -> H.Cell:
    """A tiny map deployment under the named mix: on one chip through the
    ``hash_map`` driver, on more through ``sharded_map``."""
    config = dict(TINY_MAP, name=f"tiny-map-x{chips}",
                  driver="hash_map" if chips == 1 else "sharded_map")
    return H.Cell(f"tiny-map-{traffic}-x{chips}", chips, config,
                  H.load_json(H.BENCH / "traffic" / f"{traffic}.json"),
                  H.load_json(H.ROOT / "BENCHMARK.json"))


def tiny_driver(cell, seed: int, seconds: float = 1.0):
    """A driver of the cell (or the named cell of BENCHMARK.json) at tiny
    size on the first devices JAX has (the chip check is the harness's,
    and is skipped here)."""
    import jax
    if isinstance(cell, str):
        cell = tiny_cell(cell)
    mod = H.driver_module(cell.config["driver"])
    return mod.Driver(cell, seed, jax.devices()[:cell.chips], seconds)


def run_tiny(cell, seed: int, seconds: float = 1.0) -> H.Outcome:
    """Set-up, window and checks of one run, as ``run.py`` makes them."""
    d = tiny_driver(cell, seed, seconds)
    d.setup()
    with H.CompileCounter() as counter:
        d.window(seconds, None)
    out = d.check()
    out.compiles = counter.compiles
    return out
