"""Six processes load the native library at the same moment (what six
xdist workers do at collection, through the ``skipif`` of every native
test file): the compiler runs once, or never where the library is already
in place, and nobody meets a partial file.

Driven on a copy of the package under ``tmp_path`` so the checkout's own
library is neither needed nor disturbed; the Makefile takes ``CXX`` from
the environment, and a wrapper that logs a line before calling ``g++``
counts the compiler's runs.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

_PKG = Path(__file__).resolve().parents[1] / "dragonfly2_tpu"
_LOADERS = 6

_CHILD = """
import json, sys
sys.path.insert(0, sys.argv[1])
from dragonfly2_tpu import native
assert native.__file__.startswith(sys.argv[1]), native.__file__
print("ready", flush=True)
sys.stdin.readline()
lib = native.load()
print(json.dumps({"available": lib is not None, "error": native.build_error()}))
"""

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("make") is None,
    reason="no g++ / make to build the native library with",
)


def _load_at_once(root: Path, env: dict) -> list:
    children = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(root)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env, cwd=str(root),
        )
        for _ in range(_LOADERS)
    ]
    try:
        for child in children:
            assert child.stdout.readline().strip() == "ready", child.stderr.read()
        for child in children:
            child.stdin.write("\n")
            child.stdin.flush()
        return [
            json.loads(child.communicate(timeout=300)[0]) for child in children
        ]
    finally:
        for child in children:
            if child.poll() is None:
                child.kill()
                child.wait()


@pytest.mark.parametrize("start", ["no_library", "library_in_place"])
def test_six_simultaneous_loads_build_at_most_once(tmp_path, start):
    shutil.copytree(
        _PKG, tmp_path / "dragonfly2_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so", "*.so.tmp.*", ".build.lock"),
    )
    native_dir = tmp_path / "dragonfly2_tpu" / "native"
    compiler_runs = tmp_path / "compiler_runs"
    compiler_runs.touch()
    cxx = tmp_path / "cxx"
    cxx.write_text(
        f'#!/bin/sh\necho run >> "{compiler_runs}"\nexec g++ "$@"\n'
    )
    cxx.chmod(0o755)
    if start == "library_in_place":
        subprocess.run(["make", "-C", str(native_dir), "-s"], check=True, timeout=120)
    env = dict(os.environ, CXX=str(cxx))

    results = _load_at_once(tmp_path, env)

    assert results == [{"available": True, "error": None}] * _LOADERS
    runs = len(compiler_runs.read_text().splitlines())
    assert runs == (1 if start == "no_library" else 0)
    assert [p.name for p in native_dir.glob("*.so*")] == ["libdragonfly_native.so"]
