"""The command's exits, and the count of compiles inside a window."""
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp

from bench import common, registry, run

ARGS = ["--workload", "paper-ap-2e20", "--seed", "1", "--seconds", "1"]


def test_no_tpu_exits_non_zero_and_prints_no_result(capsys):
    assert run.main(ARGS) == run.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


def test_without_the_program_it_exits_non_zero(tmp_path):
    shutil.copytree(registry.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py"] + ARGS,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_cell_exits_non_zero(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]
                    ) == run.EXIT_BAD_CELL
    assert capsys.readouterr().out == ""


def test_compiles_inside_the_window_are_counted():
    counter = common.CompileCounter()
    counter.on = True
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    counter.on = False
    assert counter.total >= 1
    before = counter.total
    jax.jit(lambda x: x - 2)(jnp.arange(5.0)).block_until_ready()
    assert counter.total == before


def test_references_import_nothing_of_the_program():
    for path in (registry.BENCH_DIR / "reference").glob("*.py"):
        text = path.read_text()
        assert "repro" not in text, path
