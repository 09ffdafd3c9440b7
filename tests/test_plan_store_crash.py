"""Plan-store crash consistency: a writer killed mid-save never corrupts it.

``PlanRegistry.save`` is stage-then-commit (write + fsync ``{path}.tmp.{pid}``,
then ``os.replace``), and every ``save_plan_store`` writer stages inside the
flock'd merge lock, which the OS releases on process death.  So for either
crash window —

* **mid-stage** (died while writing the temp file): the temp holds torn JSON
  but the committed store was never touched;
* **mid-commit** (died between fsync and rename): a complete-but-orphaned
  temp file sits next to the untouched store —

the invariant is the same: the store at ``path`` stays loadable with its
previous contents, and the next ``save_plan_store`` garbage-collects the
``.tmp`` litter while merging in its own plans.  This pins down the latent
single-writer assumption the replicated serving tier (ISSUE 7) now violates
by design: N replicas all periodically merge into one shared store.

Crashes are real ``os._exit`` process deaths in subprocesses, not exceptions.
"""
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.engine import PlanRegistry

_CRASH_SCRIPT = textwrap.dedent(
    """
    import os, sys
    store, point = sys.argv[1], sys.argv[2]
    os.environ.pop("REPRO_PLAN_STORE", None)
    from repro.core import engine
    from repro.core.engine import Engine, plan_cache_for, save_plan_store
    from repro.core.template import TemplateConfig

    eng = Engine(TemplateConfig(backend="pallas"),
                 plan_cache=plan_cache_for())
    eng.plan_gemm(64, 64, 64)
    save_plan_store(store)          # complete store: 1 entry
    eng.plan_gemm(128, 64, 64)      # second entry, never committed

    if point == "commit":
        real = os.replace
        def boom(src, dst, *a, **kw):
            if dst == store:
                os._exit(7)         # die after fsync, before the rename
            return real(src, dst, *a, **kw)
        os.replace = boom
    elif point == "stage":
        def boom(doc, f, **kw):
            f.write('{"version": 99, "torn')
            f.flush()
            os._exit(7)             # die mid-write: torn temp file
        engine.json.dump = boom
    else:
        raise SystemExit(f"bad crash point {point!r}")
    save_plan_store(store)
    os._exit(1)                     # the crash above must have fired
    """
)

_RECOVER_SCRIPT = textwrap.dedent(
    """
    import glob, json, os, sys
    store = sys.argv[1]
    os.environ.pop("REPRO_PLAN_STORE", None)
    from repro.core.engine import (Engine, PlanRegistry, plan_cache_for,
                                   save_plan_store)
    from repro.core.template import TemplateConfig

    eng = Engine(TemplateConfig(backend="pallas"),
                 plan_cache=plan_cache_for())
    eng.plan_gemm(128, 64, 64)
    save_plan_store(store)
    reg = PlanRegistry()
    print(json.dumps({"entries": reg.load(store),
                      "litter": glob.glob(store + ".tmp.*")}))
    """
)


def _run(script, *argv):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run(
        [sys.executable, "-c", script, *argv], capture_output=True, text=True,
        env=env, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


@pytest.mark.parametrize("point", ["stage", "commit"])
def test_writer_killed_mid_save_leaves_loadable_store(tmp_path, point):
    store = str(tmp_path / "plans.json")
    out = _run(_CRASH_SCRIPT, store, point)
    assert out.returncode == 7, (
        f"crash writer exited {out.returncode}, wanted the simulated kill:\n"
        f"{out.stderr[-3000:]}")

    # previous committed store: untouched, loadable, still 1 entry
    reg = PlanRegistry()
    assert reg.load(store) == 1
    assert len(reg) == 1

    # the dead writer left tmp litter behind (and, mid-stage, it is torn —
    # proving the commit really is what publishes)
    litter = glob.glob(store + ".tmp.*")
    assert litter, "crashed writer should leave a .tmp sibling"
    if point == "stage":
        with pytest.raises(json.JSONDecodeError):
            with open(litter[0]) as f:
                json.load(f)

    # next writer merges its plans in and garbage-collects the litter
    out2 = _run(_RECOVER_SCRIPT, store)
    assert out2.returncode == 0, out2.stderr[-3000:]
    rec = json.loads(out2.stdout.strip().splitlines()[-1])
    assert rec["entries"] == 2, rec  # old 64-gemm + recovered 128-gemm
    assert rec["litter"] == [], rec
    assert glob.glob(store + ".tmp.*") == []
