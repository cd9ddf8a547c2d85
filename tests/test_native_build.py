"""Built from what git would commit: the native library's staleness rule
and the package list.

A checkout or a copied tree keeps no useful mtimes, and ``native/build/``
is git-ignored, so the on-demand build decides from a hash of the sources
and flags stored beside the ``.so``. The two builds below run in a child
process against a scratch build directory: the test process's own loaded
library is never touched.
"""

import glob
import os
import subprocess
import sys
import tomllib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["REPO"])
import dmlc_tpu.native as n

build = os.environ["BUILD"]
n._BUILD_DIR = build
n._SO_PATH = os.path.join(build, "libdmlc_tpu_native.so")
n._HASH_PATH = n._SO_PATH + ".srchash"
out = {}

# 1. a clean tree (no build directory at all) builds and loads
assert not os.path.exists(build)
out["clean_available"] = n.available()
out["hash_recorded"] = n._recorded_hash() == n._source_hash()
first = os.stat(n._SO_PATH)

# 2. same sources, same hash: a second load in a fresh state does NOT rebuild
n._lib = None
assert n.available()
out["rebuilt_when_fresh"] = os.stat(n._SO_PATH).st_ino != first.st_ino

# 3. the recorded hash differs from the tree, and the .so is the NEWEST
#    file around (an mtime rule would trust it): it must be rebuilt
with open(n._HASH_PATH, "w") as f:
    f.write("0" * 64 + "\n")
future = time.time() + 3600
os.utime(n._SO_PATH, (future, future))
n._lib = None
out["stale_available"] = n.available()
out["rebuilt_when_stale"] = os.stat(n._SO_PATH).st_ino != first.st_ino
out["hash_repaired"] = n._recorded_hash() == n._source_hash()
out["parses"] = len(n.parse_libsvm(b"1 0:1.5 3:2\n0 1:1\n")["label"]) == 2
print(json.dumps(out))
"""


@pytest.mark.skipif(
    subprocess.run(["which", "g++"], capture_output=True).returncode != 0,
    reason="no g++ on this host")
def test_native_rebuilds_on_source_hash_not_mtime(tmp_path):
    import json

    env = dict(os.environ, REPO=REPO, BUILD=str(tmp_path / "build"))
    env.pop("DMLC_TPU_NO_NATIVE", None)
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {
        "clean_available": True, "hash_recorded": True,
        "rebuilt_when_fresh": False,
        "stale_available": True, "rebuilt_when_stale": True,
        "hash_repaired": True, "parses": True,
    }, out


def test_source_hash_covers_sources_headers_and_flags(monkeypatch):
    import dmlc_tpu.native as n

    base = n._source_hash()
    assert base == n._source_hash()  # deterministic
    monkeypatch.setenv("DMLC_TPU_SANITIZE", "address")
    assert n._source_hash() != base  # the flags are part of it
    monkeypatch.delenv("DMLC_TPU_SANITIZE")
    monkeypatch.setattr(n, "_HDRS", n._HDRS[:-1])
    assert n._source_hash() != base  # and so is every header


def test_pyproject_lists_every_package():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        listed = set(tomllib.load(f)["tool"]["setuptools"]["packages"])
    on_disk = {os.path.relpath(os.path.dirname(p), REPO).replace(os.sep, ".")
               for p in glob.glob(os.path.join(REPO, "dmlc_tpu", "**",
                                               "__init__.py"),
                                  recursive=True)}
    assert listed == on_disk


def _listed_sources(where):
    """The ``.cc`` files one of the four hand-kept source lists names
    (each says "keep in sync with the other three")."""
    import re

    if where == "dmlc_tpu/native/__init__.py":
        import dmlc_tpu.native as n

        return sorted(os.path.basename(p) for p in n._SRCS)
    with open(os.path.join(REPO, where)) as f:
        text = f.read()
    pattern = {
        "Makefile": r"^NATIVE_SRCS = ((?:.*\\\n)*.*)$",
        "native/run_sanitizers.sh": r'^SRCS="(.*)"$',
        "native/CMakeLists.txt":
            r"add_library\(dmlc_tpu_native SHARED([^)]*)\)",
    }[where]
    (body,) = re.findall(pattern, text, re.M)
    return sorted(os.path.basename(t) for t in body.replace("\\", " ").split()
                  if t.endswith(".cc"))


@pytest.mark.parametrize("where", [
    "dmlc_tpu/native/__init__.py", "Makefile", "native/run_sanitizers.sh",
    "native/CMakeLists.txt"])
def test_native_source_list_is_the_sources_on_disk(where):
    """The library is built from exactly the translation units under
    ``native/src`` — three since PR 28 — whichever of the four lists does
    the building: a ``.cc`` missing from one is a silent gap in the
    on-demand build's hash, the sanitizers' coverage or ``make
    native-test``; a deleted one still listed breaks that build."""
    on_disk = sorted(os.path.basename(p) for p in
                     glob.glob(os.path.join(REPO, "native", "src", "*.cc")))
    assert on_disk == ["parse.cc", "reader.cc", "recordio.cc"]
    assert _listed_sources(where) == on_disk
