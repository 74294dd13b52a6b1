"""The ONE placement of the persistent compile cache
(utils/compile_cache, ISSUE 22): obey ``JAX_COMPILATION_CACHE_DIR``
by setting nothing in code, else the fixed ``<checkout>/.jax_cache``;
and the repo-wide disciplines that keep it — and the chip — single:
no other line sets the cache directory, nothing sets the
several-libtpu-loads variable, nothing describes a TPU topology at
import.
"""

import ast
import os

import jax
import pytest

from ziria_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _repo_files(exts):
    skip = {".git", ".jax_cache", "__pycache__", "chiprun_out",
            ".chip_archive", ".pytest_cache", ".hypothesis"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for f in files:
            if f.endswith(exts):
                yield os.path.join(root, f)


@pytest.fixture
def updates(monkeypatch):
    """Record jax.config.update calls instead of making them (the
    suite's own cache placement must survive this file)."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    return seen


def test_env_placed_cache_sets_no_directory_in_code(monkeypatch,
                                                    updates, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "elsewhere"))
    assert compile_cache.place() == str(tmp_path / "elsewhere")
    assert "jax_compilation_cache_dir" not in [k for k, _v in updates]


def test_default_is_the_fixed_checkout_path(monkeypatch, updates):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.place() == want
    assert ("jax_compilation_cache_dir", want) in updates
    # fixed: the same answer every call, nothing of the process in it
    assert compile_cache.place() == compile_cache.checkout_dir() == want
    assert str(os.getpid()) not in want


def test_the_default_path_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_line_of_the_repo_sets_the_cache_directory():
    own = os.path.join(REPO, "ziria_tpu", "utils", "compile_cache.py")
    setters = []
    for path in _repo_files((".py", ".sh")):
        if path in (own, os.path.abspath(__file__)):
            continue
        with open(path, encoding="utf-8") as f:
            if "jax_compilation_cache_dir" in f.read():
                setters.append(os.path.relpath(path, REPO))
    # the CLI test reads the setting back; nothing else may name it
    assert setters == ["tests/test_cli_src.py"], setters


def test_nothing_in_the_repo_allows_several_libtpu_loads():
    name = "ALLOW_MULTIPLE_" + "LIBTPU_LOAD"
    hits = []
    for path in _repo_files((".py", ".sh", ".ini", ".cfg", ".toml",
                             ".json", ".yml", ".yaml")):
        with open(path, encoding="utf-8", errors="replace") as f:
            if name in f.read():
                hits.append(os.path.relpath(path, REPO))
    assert hits == []


def test_topology_is_described_only_inside_test_functions():
    """Describing a topology loads libtpu, which one process at a time
    may hold: the call is made in tests/test_tpu_compile.py's fixtures
    and nowhere at import time, anywhere."""
    name = "get_topology_" + "desc"
    users = []
    for path in _repo_files((".py",)):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        if name not in src:
            continue
        users.append(os.path.relpath(path, REPO))
        tree = ast.parse(src)
        inside = set()
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inside |= {id(n) for n in ast.walk(fn)}
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr == name:
                assert id(node) in inside, \
                    f"{path}:{node.lineno} describes a topology at " \
                    f"import"
    assert users == ["tests/test_tpu_compile.py"], users
