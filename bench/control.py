"""Readings that set the limits of ``correct``: the program's, over many
seeds, and the control's, which has to fail.  Run on the chip; the
benchmark's own runs never run this.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 8

Serving cells: per seed, one run of the cell as ``run.py`` makes it
(set-up, a window of ``--seconds`` at the cell's own load, the checks)
prints the program's widest logit gap; then the control, the reference
itself with every matrix product rounded to float8 (e4m3, scaled per
tensor; the configuration serves bf16), reads at the same positions of
the same requests the gap of the token it would put first.  That reading
takes the program's place in the run's own checks (``H.Check`` at the
configuration's limit), and the control's ``correct`` is printed beside
the program's: it has to come out false.

Map cells: the control breaks the configuration's guarantee: one update
call in four, the load's included, is acknowledged (its ``ok`` flags
returned) but its state is dropped, so a later read misses an
acknowledged write; a read-only mix meets it in the load.  Each seed
prints the mismatch counts of the program, whole, and of the control.
"""
import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness as H  # noqa: E402


def serve_control(cell, seed, devices, seconds):
    from bench.drivers import serve as S
    d = S.Driver(cell, seed, devices, seconds)
    d.setup()
    d.window(seconds, None)
    out = d.check()
    prompts, served, ref_logits = d.last_sample
    ref = S.reference_module(cell.config)
    ctrl = ref.Reference(cell.config, seed, "fp8").served_logits(prompts,
                                                                 served)
    chosen = [lg.argmax(axis=-1) for lg in ctrl]
    ctrl_gap = ref.widest_gap(ref_logits, chosen)
    control = H.Outcome(out.attempted, out.failed, [
        H.Check(c.name, ctrl_gap, c.limit) if c.name == "logit_gap" else c
        for c in out.checks])
    for who, o in (("program", out), ("control", control)):
        for c in o.checks:
            H.log(f"{who} check {c.name}: {c.value!r} (limit {c.limit!r}) "
                  f"{'ok' if c.ok else 'FAILED'}")
    gap = {c.name: c.value for c in out.checks}["logit_gap"]
    return {"program_logit_gap": gap,
            "control_logit_gap": ctrl_gap,
            "control_flips": int(sum((c != s).sum()
                                     for c, s in zip(chosen, served))),
            "correct": out.correct,
            "control_correct": control.correct}


def map_control(cell, seed, devices, seconds, broken: bool):
    mod = H.driver_module(cell.config["driver"])

    class Control(mod.Driver):
        def update(self, state, ops, ks, vs):
            new, ok, fl = super().update(state, ops, ks, vs)
            self.n_updates = getattr(self, "n_updates", 0) + 1
            if broken and self.n_updates % 4 == 0:
                return state, ok, fl       # acknowledged, then lost
            return new, ok, fl

    d = Control(cell, seed, devices, seconds)
    d.setup()
    d.window(seconds, None)
    out = d.check()
    return {c.name: c.value for c in out.checks} | {"correct": out.correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args(argv)
    cell = H.find_cell(args.workload)
    try:
        devices = H.require_chips(cell.chips)
    except H.NoChip as e:
        H.log(f"control: {e}")
        return 3
    H.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if cell.config["driver"] == "serve":
            res = serve_control(cell, seed, devices, args.seconds)
        else:
            res = {}
            if not args.control_only:
                res["program"] = map_control(cell, seed, devices,
                                             args.seconds, False)
            res["control"] = map_control(cell, seed, devices, args.seconds,
                                         True)
        print(f"seed {seed}: {res} ({time.perf_counter() - t:.1f} s)",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
