"""Repo lint rules (ISSUE 9: tools/lint_repro.py) — unit tests on
``lint_source`` plus the repo-wide pass that backs ``make lint``."""

import os
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

from lint_repro import (  # noqa: E402
    check_kernel_coverage,
    lint_source,
    main,
)


def _lint(src):
    return lint_source(textwrap.dedent(src), "x.py")


# -------------------------------------------------------- deprecated-call

def test_flags_deprecated_shim_call():
    (v,) = _lint("""
        from repro.exec.runtime import build_train_step
        step, ex = build_train_step(prog, mesh, opt)
        """)
    assert v.rule == "deprecated-call"
    assert "build_train_step" in v.message
    assert v.line == 3


def test_flags_aliased_deprecated_call():
    (v,) = _lint("""
        import repro.exec as rexec
        rexec.build_train_step(prog, mesh, opt)
        """)
    assert v.rule == "deprecated-call"
    (v,) = _lint("""
        from repro.launch import steps as st
        st.build_fcnn_program_step(prog, mesh)
        """)
    assert "build_fcnn_program_step" in v.message


def test_pragma_suppresses_deprecated_call():
    assert _lint("""
        from repro.exec.runtime import build_train_step
        build_train_step(prog, mesh, opt)  # lint: allow-deprecated
        """) == []


def test_generic_build_train_step_not_flagged():
    """launch.steps.build_train_step (the non-deprecated generic step
    builder) shares a short name with the deprecated shim — only the
    fully qualified deprecated one is flagged."""
    assert _lint("""
        from repro.launch.steps import build_train_step
        build_train_step(model, mesh, settings)
        """) == []
    assert _lint("""
        from repro.launch import steps
        steps.build_train_step(model, mesh, settings)
        """) == []


# -------------------------------------------------------- np-random-in-jit

def test_flags_np_random_in_jitted_body():
    (v,) = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + np.random.normal()
        """)
    assert v.rule == "np-random-in-jit"
    assert "np.random" in v.message or "numpy.random" in v.message


def test_flags_np_random_in_shard_map_target():
    (v,) = _lint("""
        import numpy as np
        from jax import shard_map

        def body(x):
            return x * np.random.rand()

        f = shard_map(body, mesh=m, in_specs=s, out_specs=s)
        """)
    assert v.rule == "np-random-in-jit"


def test_np_random_outside_jit_is_fine():
    assert _lint("""
        import numpy as np

        def make_batch(rng):
            return np.random.default_rng(0).normal(size=(8, 4))
        """) == []


def test_pragma_suppresses_np_random():
    assert _lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + np.random.normal()  # lint: allow-np-random
        """) == []


# --------------------------------------------------------- repo-wide pass

def test_kernel_coverage_on_this_repo():
    """Every kernel module under src/repro/kernels/ is referenced by some
    oracle test — the rule that keeps new Pallas kernels pinned."""
    assert check_kernel_coverage(REPO_ROOT) == []


def test_repo_lints_clean(capsys):
    """``make lint`` equivalent: the whole repo passes all three rules."""
    assert main(["--root", REPO_ROOT]) == 0
    out = capsys.readouterr().out
    assert "lint: OK" in out


def test_main_reports_violations(tmp_path, capsys):
    src = tmp_path / "src"
    src.mkdir()
    (src / "bad.py").write_text(
        "from repro.exec.runtime import build_train_step\n"
        "build_train_step(p, m, o)\n")
    assert main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "[deprecated-call]" in out
    assert "bad.py:2" in out
