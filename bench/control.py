"""Readings that set a configuration's check limit, on the chip.

    python3 bench/control.py --workload vgg16-224-q16.b1 --mode program --seeds 1 2 3
    python3 bench/control.py --workload vgg16-224-q16.b1 --mode control --seeds 4 5 6

Each seed is a run of the cell as ``bench/run.py`` makes it (set-up, a
short window at the cell's load, the check against the reference), all
seeds in one process.  ``--mode program`` reads the program as it is: the
lower reading.  ``--mode control`` puts the next precision down in the
program's place: the upper reading.

* fixed point: the program's own int8 path (every layer's activation
  grid moved to its int8 rung, the precision ladder of the engine);
* float32 at ``highest``: the form's reference at ``high`` (three
  bfloat16 passes) run on the chip as the forward.

One JSON line per seed: the number compared and its limit.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path

sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


@contextlib.contextmanager
def int8_rung_everywhere(form):
    """While open, the program that ``form`` binds puts every layer of its
    calibrated policy on the int8 rung of its grid."""
    from repro.core.quantization import int8_rung

    program = form.program

    def lowered(cfg):
        prog = program(cfg)

        def calibrate(*args, **kwargs):
            policy = prog.calibrate(*args, **kwargs)
            low = int8_rung(policy.fmt)
            fmts = tuple(sorted((name, low) for name in prog.layer_names()))
            return dataclasses.replace(policy, name="mixed", layer_fmts=fmts)

        return dataclasses.replace(prog, calibrate=calibrate)

    form.program = lowered
    try:
        yield
    finally:
        form.program = program


def float_high(form, cfg: dict):
    """A forward wrapper: the form's float reference at ``high`` in the
    program's place (the program's parameters are the float32 weights),
    built on the first call."""
    def wrap(forward):
        ref = None

        def run(params, x):
            nonlocal ref
            if ref is None:
                ref = form.float_reference(cfg, params, "high")
            return ref(x)

        return run

    return wrap


def control_run(cell, seed: int, seconds: float, devices, log=R.log) -> dict:
    """One run of ``cell`` with the control in the program's place."""
    if cell.cfg["reference"]["kind"] == "fixed":
        with int8_rung_everywhere(cell.form):
            return R.run_cell(cell, seed, seconds, False, devices, log=log)
    return R.run_cell(cell, seed, seconds, False, devices,
                      fault=float_high(cell.form, cell.cfg), log=log)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache

    devices = R.chips_or_exit(cell.chips)
    R.log(f"compile cache: {enable_compile_cache()}")
    for seed in args.seeds:
        if args.mode == "program":
            res = R.run_cell(cell, seed, args.seconds, False, devices)
        else:
            res = control_run(cell, seed, args.seconds, devices)
        print("READING " + json.dumps({"workload": cell.name, "mode": args.mode, "seed": seed,
                                       "correct": res["correct"], "checks": res["checks"],
                                       "attempted": res["attempted"]}), flush=True)


if __name__ == "__main__":
    main()
