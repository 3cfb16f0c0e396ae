"""Pin the driver entry points (__graft_entry__.py): the round driver
compile-checks ``entry()`` single-chip and executes ``dryrun_multichip(N)``
— a CPU simulation in a child on N virtual devices — breaking either costs
a whole round, so the suite runs both."""

import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    arr = np.asarray(out)
    assert arr.ndim == 3 and np.isfinite(arr.astype(np.float32)).all()


def test_dryrun_is_a_cpu_simulation_in_a_child(monkeypatch):
    """The dry run never probes this process's backend (a parent that has
    touched JAX holds the chip) and never runs in-process: it always spawns
    a child that forces the virtual CPU platform first."""
    calls = []
    monkeypatch.setattr(
        jax, "devices", lambda *a, **k: pytest.fail("the parent probed the backend")
    )
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: calls.append(cmd) or types.SimpleNamespace(returncode=0),
    )
    graft.dryrun_multichip(8)
    (cmd,) = calls
    code = cmd[-1]
    assert code.index("force_cpu_world(8)") < code.index("_dryrun_multichip_impl(8)")
    # a failing child is an error, not a fallback
    monkeypatch.setattr(
        graft.subprocess, "run", lambda cmd, **kw: types.SimpleNamespace(returncode=3)
    )
    with pytest.raises(RuntimeError, match="CPU-simulation child failed"):
        graft.dryrun_multichip(8)


@pytest.mark.slow  # the round driver executes this itself
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)  # asserts finite losses internally
