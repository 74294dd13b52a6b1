"""jaxlint engine + rules (ziria_tpu/analysis): per-rule fixture
snippets — one true positive and one near-miss negative each — plus
pragma suppression, the JSON schema, CLI exit codes, and the
acceptance demo: R1 re-flags a deliberately dropped cache-key
parameter in a MUTATED copy of a real rx.py jit factory.

All pure-AST and CPU-only: nothing here imports jax (pinned by
test_lint_no_jax_import in a fresh interpreter), so the whole module
is tier-1 cheap.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from ziria_tpu.analysis import lint_paths, lint_source
from ziria_tpu.analysis.__main__ import main as lint_main
from ziria_tpu.analysis.rules import RULES_BY_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RX_PY = os.path.join(REPO, "ziria_tpu", "phy", "wifi", "rx.py")


def _findings(src, rules=None, path="fixture.py"):
    rule_objs = [RULES_BY_ID[r] for r in rules] if rules else None
    return lint_source(src, path, rules=rule_objs).findings


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------------ R1

R1_TP_ENV = '''
import os
import jax
from functools import lru_cache

@lru_cache(maxsize=None)
def _jit_decode(n_sym):
    win = int(os.environ.get("ZIRIA_VITERBI_WINDOW", "0"))
    def f(x):
        return x[:win]
    return jax.jit(f)
'''

R1_TP_RESOLVER = '''
import jax
from functools import lru_cache

def fused_demap_enabled(v):
    return bool(v)

@lru_cache(maxsize=None)
def _jit_decode(n_sym):
    fused = fused_demap_enabled(None)     # mode never reaches the key
    def f(x):
        return x if fused else -x
    return jax.jit(f)
'''

R1_TP_KNOB = '''
import os
import jax
from functools import lru_cache

_WINDOW = os.environ.get("ZIRIA_WINDOW")   # module-level knob

@lru_cache(maxsize=None)
def _jit_decode(n_sym):
    def f(x):
        return x[: int(_WINDOW or 0)]
    return jax.jit(f)
'''

R1_NEGATIVE = '''
import os
import jax
from functools import lru_cache

def window_of():                 # env read OUTSIDE any factory: not R1
    return int(os.environ.get("ZIRIA_VITERBI_WINDOW", "0"))

@lru_cache(maxsize=None)
def _jit_decode(n_sym, window):  # every knob rides the cache key
    def f(x):
        return x[:window][:n_sym]
    return jax.jit(f)

def caller(x):
    return _jit_decode(4, window_of())(x)
'''


def test_r1_env_read_in_factory_flagged():
    f = _findings(R1_TP_ENV, rules=["R1"])
    assert _rules_of(f) == ["R1"] and "_jit_decode" in f[0].message


def test_r1_mode_resolver_in_factory_flagged():
    f = _findings(R1_TP_RESOLVER, rules=["R1"])
    assert _rules_of(f) == ["R1"]
    assert "fused_demap_enabled" in f[0].message


def test_r1_module_knob_in_factory_flagged():
    f = _findings(R1_TP_KNOB, rules=["R1"])
    assert _rules_of(f) == ["R1"] and "_WINDOW" in f[0].message


def test_r1_near_miss_clean():
    # the same reads OUTSIDE the factory, and a factory whose every
    # knob is a parameter, are exactly the sanctioned pattern
    assert _findings(R1_NEGATIVE, rules=["R1"]) == []


def test_r1_reflags_dropped_cache_key_param_in_real_rx_factory():
    """THE acceptance demo: take the real rx.py, drop `fused_demap`
    from `_jit_decode_data_bucketed`'s signature (= its lru_cache
    key) and resolve it inside the body instead — the exact regression
    PR 1/PR 6 closed by hand. R1 must re-flag the mutated factory,
    and the unmutated file must be clean."""
    with open(RX_PY, encoding="utf-8") as fh:
        src = fh.read()
    assert _findings(src, rules=["R1"], path=RX_PY) == []

    tree = ast.parse(src)

    class DropKeyParam(ast.NodeTransformer):
        mutated = False

        def visit_FunctionDef(self, node):
            self.generic_visit(node)
            if node.name != "_jit_decode_data_bucketed":
                return node
            assert node.args.args[-1].arg == "fused_demap"
            node.args.args = node.args.args[:-1]
            node.args.defaults = node.args.defaults[:-1]

            class Resolve(ast.NodeTransformer):
                def visit_Name(self, n):
                    if n.id == "fused_demap" and isinstance(
                            n.ctx, ast.Load):
                        return ast.copy_location(ast.Call(
                            func=ast.Name("fused_demap_enabled",
                                          ast.Load()),
                            args=[ast.Constant(None)], keywords=[]), n)
                    return n

            Resolve().visit(node)
            DropKeyParam.mutated = True
            return node

    mutated = ast.unparse(ast.fix_missing_locations(
        DropKeyParam().visit(tree)))
    assert DropKeyParam.mutated
    f = _findings(mutated, rules=["R1"], path="rx_mutated.py")
    assert f, "R1 must re-flag the dropped cache-key parameter"
    assert any("_jit_decode_data_bucketed" in x.message
               and "fused_demap_enabled" in x.message for x in f)


@pytest.mark.parametrize("factory", ["_jit_decode_data_mixed",
                                     "_jit_stream_decode_multi"])
def test_r1_guards_fused_demap_key_in_mixed_decode_factories(factory):
    """ISSUE 20 satellite: every MIXED-decode jit factory now carries
    `fused_demap` as its LAST cache-key parameter (the rate-switched
    fused front). Same demo as the bucketed factory above: AST-drop
    the parameter by position and resolve it in the body — R1 must
    re-flag each mutated factory, and the real file stays clean (the
    clean check rides the bucketed test; one parse per mutation
    here)."""
    with open(RX_PY, encoding="utf-8") as fh:
        src = fh.read()
    tree = ast.parse(src)

    class DropKeyParam(ast.NodeTransformer):
        mutated = False

        def visit_FunctionDef(self, node):
            self.generic_visit(node)
            if node.name != factory:
                return node
            assert node.args.args[-1].arg == "fused_demap"
            node.args.args = node.args.args[:-1]
            node.args.defaults = node.args.defaults[:-1]

            class Resolve(ast.NodeTransformer):
                def visit_Name(self, n):
                    if n.id == "fused_demap" and isinstance(
                            n.ctx, ast.Load):
                        return ast.copy_location(ast.Call(
                            func=ast.Name("fused_demap_enabled",
                                          ast.Load()),
                            args=[ast.Constant(None)], keywords=[]), n)
                    return n

            Resolve().visit(node)
            DropKeyParam.mutated = True
            return node

    mutated = ast.unparse(ast.fix_missing_locations(
        DropKeyParam().visit(tree)))
    assert DropKeyParam.mutated, f"{factory} not found in rx.py"
    f = _findings(mutated, rules=["R1"], path="rx_mutated.py")
    assert any(factory in x.message
               and "fused_demap_enabled" in x.message for x in f), \
        f"R1 must re-flag {factory}'s dropped fused_demap key"


# ------------------------------------------------------------------ R2

R2_TP = '''
import numpy as np
from ziria_tpu.utils import dispatch

def receive(x):
    dec = _jit_decode(4)
    with dispatch.timed("rx.decode"):
        out = np.asarray(dec(x))     # device wait billed as dispatch
    return out
'''

R2_NEGATIVE = '''
import numpy as np
from ziria_tpu.utils import dispatch

def receive(x):
    meta = np.asarray(x)             # host value: not a sync
    dec = _jit_decode(4)
    with dispatch.timed("rx.decode"):
        out = dec(meta)              # dispatch only inside the block
    return np.asarray(out)           # sync OUTSIDE the timed region
'''


def test_r2_host_sync_inside_timed_flagged():
    f = _findings(R2_TP, rules=["R2"])
    assert _rules_of(f) == ["R2"] and "np.asarray" in f[0].message


def test_r2_near_miss_clean():
    assert _findings(R2_NEGATIVE, rules=["R2"]) == []


def test_r2_builtin_sync_on_jit_result_flagged():
    src = R2_TP.replace("np.asarray(dec(x))", "float(dec(x))")
    f = _findings(src, rules=["R2"])
    assert _rules_of(f) == ["R2"] and "float" in f[0].message


# ------------------------------------------------------------------ R3

R3_TP = '''
def receive(x):
    return _jit_decode(4)(x)         # fired blind: no span, no count
'''

R3_NEGATIVE = '''
from ziria_tpu.utils import dispatch

def receive(x):
    dec = _jit_decode(4)             # building the callable is free
    with dispatch.timed("rx.decode"):
        return dec(x)
'''


def test_r3_untimed_dispatch_flagged():
    f = _findings(R3_TP, rules=["R3"])
    assert _rules_of(f) == ["R3"] and "_jit_decode" in f[0].message


def test_r3_near_miss_clean():
    assert _findings(R3_NEGATIVE, rules=["R3"]) == []


def test_r3_self_attr_dispatch_tracked():
    src = '''
from ziria_tpu.utils import dispatch

class Rx:
    def __init__(self):
        self._jit1 = _jit_chunk(8)
    def scan(self, x):
        return self._jit1(x)
'''
    f = _findings(src, rules=["R3"])
    assert _rules_of(f) == ["R3"] and "self._jit1" in f[0].message


# ------------------------------------------------------------------ R4

R4_TP_IMPORT_TIME = '''
import os
DEBUG = os.environ.get("ZIRIA_DEBUG")
'''

R4_TP_SCATTERED = '''
import os

def receive(x):
    if os.environ.get("ZIRIA_STREAMING_RX") == "0":
        return None
    return x
'''

R4_TP_WRITE = '''
import os

def set_flag():
    os.environ["ZIRIA_STREAMING_RX"] = "0"
'''

R4_NEGATIVE = '''
import os

def streaming_rx_enabled(v=None):     # THE designated single reader
    if v is not None:
        return v
    return os.environ.get("ZIRIA_STREAMING_RX", "1") != "0"

def env_trace_path():
    return os.environ.get("ZIRIA_TRACE") or None
'''


def test_r4_import_time_read_flagged():
    f = _findings(R4_TP_IMPORT_TIME, rules=["R4"])
    assert _rules_of(f) == ["R4"] and "import time" in f[0].message


def test_r4_scattered_read_flagged():
    f = _findings(R4_TP_SCATTERED, rules=["R4"])
    assert _rules_of(f) == ["R4"] and "single-reader" in f[0].message


def test_r4_env_write_flagged():
    f = _findings(R4_TP_WRITE, rules=["R4"])
    assert _rules_of(f) == ["R4"] and "write" in f[0].message


def test_r4_designated_readers_clean():
    assert _findings(R4_NEGATIVE, rules=["R4"]) == []


# ------------------------------------------------------------------ R5

R5_TP_ANNOTATION = '''
import numpy as np
from functools import lru_cache

@lru_cache(maxsize=None)
def _table(x: np.ndarray):
    return x.sum()
'''

R5_TP_NESTED = '''
from functools import lru_cache

def build(arr):
    @lru_cache(maxsize=None)         # new cache per build() call,
    def _inner(n):                   # closing over arr
        return arr[:n]
    return _inner
'''

R5_TP_CALLSITE = '''
import numpy as np
import jax
from functools import lru_cache

@lru_cache(maxsize=None)
def _jit_decode(x):
    return jax.jit(lambda y: y)

def go(samples):
    return _jit_decode(np.asarray(samples))
'''

R5_NEGATIVE = '''
import jax
from functools import lru_cache

@lru_cache(maxsize=None)
def _jit_decode(rate_mbps: int, n_sym_bucket: int, window: int):
    return jax.jit(lambda y: y)

def go(samples):
    return _jit_decode(6, 8, 0)(samples)
'''


def test_r5_array_annotation_flagged():
    f = _findings(R5_TP_ANNOTATION, rules=["R5"])
    assert _rules_of(f) == ["R5"] and "'x'" in f[0].message


def test_r5_nested_lru_cache_flagged():
    f = _findings(R5_TP_NESTED, rules=["R5"])
    assert _rules_of(f) == ["R5"] and "inside another function" \
        in f[0].message


def test_r5_array_callsite_flagged():
    f = _findings(R5_TP_CALLSITE, rules=["R5"])
    assert _rules_of(f) == ["R5"] and "np.asarray" in f[0].message


def test_r5_scalar_keys_clean():
    assert _findings(R5_NEGATIVE, rules=["R5"]) == []


# ------------------------------------------------------------------ R6

R6_TP_BUCKET_FLOOR = '''
from ziria_tpu.utils.dispatch import pow2_bucket

def n_sym_bucket(n_sym):
    return pow2_bucket(n_sym, 4)         # literal floor forks Geometry
'''

R6_TP_BUCKET_KW = '''
from ziria_tpu.utils import dispatch

def cap_bucket(n):
    return dispatch.pow2_bucket(n, min_bucket=1 << 9)
'''

R6_TP_TUNABLE_KW = '''
import jax
from functools import lru_cache

@lru_cache(maxsize=None)
def _jit_decode(n_sym_bucket, viterbi_window=0):
    return jax.jit(lambda y: y)

def go(samples, n):
    return _jit_decode(n, viterbi_window=64)(samples)
'''

R6_NEGATIVE = '''
import jax
from functools import lru_cache
from ziria_tpu.utils.dispatch import pow2_bucket

@lru_cache(maxsize=None)
def _jit_decode(n_sym_bucket, viterbi_window=0):
    return jax.jit(lambda y: y)

def go(samples, n, geo):
    b = pow2_bucket(n, geo.sym_bucket_min)   # floor from Geometry: ok
    w = geo.resolve().viterbi_window
    return _jit_decode(b, viterbi_window=w)(samples)

def configure(report):
    # a KNOWN tunable keyword at a NON-factory call: not R6's business
    return report(chunk_len=8192)

def shape_literal(samples):
    # positional literals are shape-like plumbing, not named tunables
    return _jit_decode(8)(samples)
'''


def test_r6_literal_bucket_floor_flagged():
    f = _findings(R6_TP_BUCKET_FLOOR, rules=["R6"])
    assert _rules_of(f) == ["R6"] and "pow2_bucket floor" in \
        f[0].message
    f = _findings(R6_TP_BUCKET_KW, rules=["R6"])
    assert _rules_of(f) == ["R6"] and "1 << 9" in f[0].message


def test_r6_literal_tunable_keyword_flagged():
    f = _findings(R6_TP_TUNABLE_KW, rules=["R6"])
    assert _rules_of(f) == ["R6"]
    assert "viterbi_window=64" in f[0].message
    assert "Geometry" in f[0].message


def test_r6_near_miss_clean():
    assert _findings(R6_NEGATIVE, rules=["R6"]) == []


def test_r6_is_registered_and_tree_is_clean():
    # the shipped tree itself passes the new rule — no suppressions
    # were added to buy this (the cli pragma file predates R6)
    assert "R6" in RULES_BY_ID
    src_root = os.path.join(REPO, "ziria_tpu")
    res = lint_paths([src_root], rules=[RULES_BY_ID["R6"]])
    assert [f.message for f in res.findings] == []


# ------------------------------------------------- pragmas + engine

def test_pragma_suppresses_same_and_previous_line():
    same = R4_TP_SCATTERED.replace(
        'os.environ.get("ZIRIA_STREAMING_RX") == "0":',
        'os.environ.get("ZIRIA_STREAMING_RX") == "0":  '
        '# ziria: lint-ignore[R4] fixture justification')
    assert _findings(same, rules=["R4"]) == []
    prev = R4_TP_SCATTERED.replace(
        "    if os.environ",
        "    # ziria: lint-ignore[R4] fixture justification\n"
        "    if os.environ")
    assert _findings(prev, rules=["R4"]) == []


def test_file_pragma_suppresses_whole_file():
    src = "# ziria: lint-ignore-file[R4] fixture justification\n" \
        + R4_TP_SCATTERED + R4_TP_WRITE.replace("import os\n", "")
    res = lint_source(src, "f.py",
                      rules=[RULES_BY_ID["R4"]])
    assert res.findings == [] and res.suppressed == 2


def test_pragma_without_reason_is_itself_a_finding():
    src = R4_TP_SCATTERED.replace(
        '== "0":', '== "0":  # ziria: lint-ignore[R4]')
    f = _findings(src, rules=["R4"])
    assert _rules_of(f) == ["lint"]
    assert "justification" in f[0].message


def test_pragma_does_not_cover_other_rules():
    src = R4_TP_SCATTERED.replace(
        '== "0":', '== "0":  # ziria: lint-ignore[R1] wrong rule id')
    f = _findings(src, rules=["R4"])
    assert _rules_of(f) == ["R4"]


def test_pragma_in_string_literal_does_not_suppress():
    """Only real COMMENT tokens register: a docstring that merely
    QUOTES the pragma syntax (docs, examples) must never become a
    live whole-file suppression."""
    src = (
        '"""Suppress with `# ziria: lint-ignore-file[R4] reason`."""\n'
        + R4_TP_SCATTERED)
    f = _findings(src, rules=["R4"])
    assert _rules_of(f) == ["R4"]


def test_unused_pragma_is_a_finding():
    """A pragma whose finding was since fixed is stale creep — it
    would silently mask the NEXT finding of that rule there."""
    src = ("import os\n"
           "# ziria: lint-ignore[R4] justified once, finding fixed\n"
           "def env_window():\n"
           "    return os.environ.get('ZIRIA_WINDOW')\n")
    f = _findings(src)
    assert _rules_of(f) == ["lint"]
    assert "unused" in f[0].message and f[0].line == 2


def test_unused_pragma_not_reported_for_unrun_rules():
    """Under a --rules subset, 'unused' is undecidable for the rules
    that did not run — their pragmas are left alone."""
    src = ("import os\n"
           "# ziria: lint-ignore[R4] justified once, finding fixed\n"
           "def env_window():\n"
           "    return os.environ.get('ZIRIA_WINDOW')\n")
    assert _findings(src, rules=["R1"]) == []


def test_syntax_error_is_a_finding_not_a_crash():
    f = _findings("def broken(:\n", rules=["R1"])
    assert _rules_of(f) == ["lint"] and "syntax" in f[0].message


# ------------------------------------------------- JSON + CLI surface

def test_json_schema(tmp_path):
    p = tmp_path / "tp.py"
    p.write_text(R4_TP_SCATTERED + R3_TP)
    res = lint_paths([str(tmp_path)])
    doc = json.loads(res.to_json())
    assert doc["version"] == 1 and doc["files"] == 1
    assert doc["counts"] == {"R3": 1, "R4": 1}
    assert doc["suppressed"] == 0
    for f in doc["findings"]:
        assert set(f) == {"file", "line", "col", "rule", "message"}
        assert f["file"].endswith("tp.py") and f["line"] > 0


def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "ok.py").write_text(R4_NEGATIVE)   # clean under ALL rules
    assert lint_main([str(clean)]) == 0

    for i, tp in enumerate([R1_TP_ENV, R2_TP, R3_TP,
                            R4_TP_SCATTERED, R5_TP_ANNOTATION]):
        d = tmp_path / f"tp{i}"
        d.mkdir()
        (d / "bad.py").write_text(tp)
        assert lint_main([str(d)]) == 1, f"fixture {i} must fail"
    capsys.readouterr()

    assert lint_main(["--rules", "R9", str(clean)]) == 2
    assert lint_main([str(tmp_path / "nope")]) == 2
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("R1", "R2", "R3", "R4", "R5"):
        assert rid in out


def test_cli_json_flag(tmp_path, capsys):
    d = tmp_path / "j"
    d.mkdir()
    (d / "bad.py").write_text(R3_TP)
    assert lint_main(["--json", str(d)]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["counts"] == {"R3": 1}


def test_lint_no_jax_import():
    """The pure-AST contract: linting the whole tree must never pull
    in jax (the gate has to work when the TPU backend probe hangs)."""
    code = (
        "import sys\n"
        "from ziria_tpu.analysis import lint_paths\n"
        "lint_paths([r'%s'])\n"
        "assert 'jax' not in sys.modules, 'lint imported jax'\n"
        % os.path.join(REPO, "ziria_tpu"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
