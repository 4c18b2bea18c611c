"""Tier-1 enforcement of the static correctness layer (docs/static-analysis.md).

Four layers, one gate each:

* the cross-language invariant linter (``scripts/check_invariants.py``) must
  exit 0 on the tree with its FULL rule set active — a renamed env var, an
  undocumented metric or flag, a drifted wire-frame tag, an atomic op off
  its declared ordering protocol, or a C-export/ctypes-table mismatch fails
  here instead of corrupting a 256-chip job;
* the thread-role checker (``scripts/check_threadroles.py``) must exit 0
  with ROLE-COVERAGE / ROLE-CALL / SIGNAL-SAFE all active — deleting a
  single HVDTPU_CALLED_ON annotation is a lint failure, not a silent
  contract loss;
* every rule of both checkers must actually fire — proven against the
  negative fixtures under ``tests/data/lint_fixtures/``, down to the
  file:line the finding anchors on;
* the clang-dependent targets (``make analyze`` / ``make tidy``) must at
  minimum skip cleanly on clang-less boxes (on CI, with clang installed,
  they are the thread-safety / clang-tidy gates).

No clang, jax, or network required anywhere in this file.
"""

import ast
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINTER = os.path.join(REPO, "scripts", "check_invariants.py")
ROLE_CHECKER = os.path.join(REPO, "scripts", "check_threadroles.py")
FIXTURES = os.path.join(REPO, "tests", "data", "lint_fixtures")
NATIVE = os.path.join(REPO, "horovod_tpu", "native")

# Every rule the linter must run on the real tree. ENUM-MIRROR lists its
# enum pairs so a silently-unparseable enum (file moved, regex rotted)
# fails loudly here rather than skipping the check forever.
EXPECTED_RULES = ["ENV-DECL", "ENV-DOC", "ENV-RAW", "MET-DOC", "FLAG-DOC",
                  "ATOMIC-DISCIPLINE", "ABI-MIRROR"]
EXPECTED_ENUM_PAIRS = ["DataType", "OpType", "CtrlMsg", "ResponseType",
                       "WireCompression", "ReduceOp", "AllreduceAlgo",
                       "HierMode"]


def run_linter(root=None):
    cmd = [sys.executable, LINTER]
    if root is not None:
        cmd += ["--root", root]
    return subprocess.run(cmd, capture_output=True, text=True, timeout=120)


class TestTreeIsClean:
    def test_linter_exits_zero_on_the_tree(self):
        r = run_linter()
        assert r.returncode == 0, \
            f"invariant linter found drift:\n{r.stdout}{r.stderr}"

    def test_all_rules_ran(self):
        # The linter skips rules whose inputs are missing (that is what
        # keeps fixtures small) — so the real tree must prove none skipped.
        r = run_linter()
        summary = r.stderr
        for rule in EXPECTED_RULES:
            assert rule in summary, f"rule {rule} did not run: {summary}"
        m = re.search(r"ENUM-MIRROR\(([^)]*)\)", summary)
        assert m, f"no enum pairs ran: {summary}"
        ran = set(m.group(1).split(","))
        missing = set(EXPECTED_ENUM_PAIRS) - ran
        assert not missing, f"enum pairs not checked: {sorted(missing)}"


# (fixture dir, expected exit, [(relpath, line, rule, message-fragment)])
FIXTURE_CASES = [
    ("clean", 0, []),
    ("undeclared_env", 1, [
        ("horovod_tpu/uses.py", 4, "ENV-DECL", "HVDTPU_NOT_DECLARED"),
    ]),
    ("env_doc_drift", 1, [
        ("horovod_tpu/utils/envvars.py", 3, "ENV-DOC",
         "HVDTPU_UNDOCUMENTED is declared but has no row"),
        ("horovod_tpu/utils/envvars.py", 4, "ENV-DOC",
         "HVDTPU_MISFILED_INTERNAL is in INTERNAL_ENV_VARS but not "
         "documented under"),
        ("docs/envvars.md", 2, "ENV-DOC",
         "HVDTPU_GONE is documented but not declared"),
    ]),
    ("raw_environ", 1, [
        ("horovod_tpu/rawuser.py", 7, "ENV-RAW", "HVDTPU_RAWREAD"),
        ("horovod_tpu/rawuser.py", 8, "ENV-RAW", "HVDTPU_RAWREAD"),
        ("horovod_tpu/rawuser.py", 9, "ENV-RAW", "HVDTPU_RAWREAD"),
        ("horovod_tpu/rawuser.py", 11, "ENV-RAW", "HVDTPU_RAWREAD"),
    ]),
    ("undocumented_metric", 1, [
        ("horovod_tpu/native/instrument.cpp", 4, "MET-DOC",
         "hvdtpu_fixture_missing_total"),
        ("docs/metrics.md", 8, "MET-DOC", "hvdtpu_fixture_stale_total"),
    ]),
    ("mismatched_frame_tag", 1, [
        ("horovod_tpu/basics.py", 2, "ENUM-MIRROR",
         "'peers' is 2 here but PEERS=3"),
    ]),
    ("undocumented_flag", 1, [
        ("horovod_tpu/runner/launch.py", 8, "FLAG-DOC", "--ghost-flag"),
        ("horovod_tpu/runner/launch.py", 9, "FLAG-DOC", "--prose-only-flag"),
        ("docs/runner.md", 11, "FLAG-DOC", "--stale-flag"),
    ]),
    ("atomic_undeclared", 1, [
        ("horovod_tpu/native/ring.h", 10, "ATOMIC-DISCIPLINE",
         "count_ declares no ordering protocol"),
    ]),
    ("atomic_order_mismatch", 1, [
        ("horovod_tpu/native/ring.h", 7, "ATOMIC-DISCIPLINE",
         "count_.load: no explicit memory_order (defaults to seq_cst)"),
    ]),
    ("abi_unregistered_export", 1, [
        ("horovod_tpu/native/core.cpp", 8, "ABI-MIRROR",
         "export hvdtpu_fixture_new has no _C_API registration"),
    ]),
    ("abi_arity_mismatch", 1, [
        ("horovod_tpu/basics.py", 3, "ABI-MIRROR",
         "hvdtpu_enqueue: 1 argtypes registered but the C signature takes "
         "2 parameters"),
    ]),
    ("abi_type_mismatch", 1, [
        ("horovod_tpu/basics.py", 3, "ABI-MIRROR",
         "hvdtpu_set_chaos: argtypes[0] is c_int but the C parameter is "
         "'double'"),
    ]),
    ("abi_missing_gate", 1, [
        ("horovod_tpu/basics.py", 3, "ABI-MIRROR",
         "hvdtpu_fixture_probe: required=True but the symbol is newer than "
         "the baseline"),
    ]),
]


class TestEveryRuleFires:
    @pytest.mark.parametrize("name,exit_code,expected",
                             FIXTURE_CASES, ids=[c[0] for c in FIXTURE_CASES])
    def test_fixture(self, name, exit_code, expected):
        r = run_linter(os.path.join(FIXTURES, name))
        assert r.returncode == exit_code, \
            f"{name}: exit {r.returncode}, wanted {exit_code}:\n{r.stdout}"
        for rel, line, rule, frag in expected:
            want = f"{rel}:{line}: [{rule}]"
            hit = [l for l in r.stdout.splitlines()
                   if l.startswith(want) and frag in l]
            assert hit, (f"{name}: expected a finding '{want} ...{frag}...', "
                         f"got:\n{r.stdout}")
        assert len(r.stdout.strip().splitlines()) == len(expected), \
            f"{name}: unexpected extra findings:\n{r.stdout}"

    def test_raw_environ_fixture_allows_writes(self):
        # The write on rawuser.py:12 (launcher env injection pattern) must
        # NOT be flagged — only reads are violations.
        r = run_linter(os.path.join(FIXTURES, "raw_environ"))
        assert "rawuser.py:12" not in r.stdout


class TestRawEnvReadDetector:
    """Unit-level checks of the ENV-RAW ast matcher."""

    def _findings(self, src):
        import ast
        import importlib.util
        spec = importlib.util.spec_from_file_location("check_invariants",
                                                      LINTER)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.find_raw_env_reads(ast.parse(src))

    def test_detects_all_read_forms(self):
        src = ("import os\n"
               "a = os.environ['HVDTPU_X1']\n"
               "b = os.environ.get('HVDTPU_X2')\n"
               "c = os.getenv('HVDTPU_X3')\n"
               "d = os.environ.pop('HVDTPU_X4', None)\n"
               "e = os.environ.setdefault('HVDTPU_X5', '1')\n"
               "f = os.environ.get(ev.HVDTPU_X6)\n"
               "_KEY = 'HVDTPU_X7'\n"
               "g = os.environ[_KEY]\n"
               "_ALIAS = ev.HVDTPU_X8\n"
               "h = os.getenv(_ALIAS)\n")
        got = self._findings(src)
        assert [n for _, n in got] == [
            "HVDTPU_X1", "HVDTPU_X2", "HVDTPU_X3", "HVDTPU_X4",
            "HVDTPU_X5", "HVDTPU_X6", "HVDTPU_X7", "HVDTPU_X8"]

    def test_ignores_writes_and_foreign_keys(self):
        src = ("import os\n"
               "os.environ['HVDTPU_X'] = '1'\n"          # write
               "a = os.environ.get('JAX_PLATFORMS')\n"   # not HVDTPU_*
               "b = env.get('HVDTPU_X')\n"               # plain dict
               "c = os.environ.get(key)\n")              # dynamic key
        assert self._findings(src) == []


# (fixture dir, [(relpath, line, rule, message-fragment)]) — exit 1 each.
ROLE_FIXTURE_CASES = [
    ("role_missing_annotation", [
        ("horovod_tpu/native/shm_transport.h", 8, "ROLE-COVERAGE",
         "public method ShmTransport::Recv has no thread-role annotation"),
    ]),
    ("role_cross_call", [
        ("horovod_tpu/native/transport.cpp", 5, "ROLE-CALL",
         "Transport::Pump (role background) calls Configure (pinned to "
         "user)"),
    ]),
    ("signal_unsafe", [
        ("horovod_tpu/native/flightrec.cpp", 5, "SIGNAL-SAFE",
         "WriteRing is reachable from a signal-role root but calls "
         "async-signal-unsafe 'malloc'"),
    ]),
]


def run_role_checker(root=None):
    cmd = [sys.executable, ROLE_CHECKER]
    if root is not None:
        cmd += ["--root", root]
    return subprocess.run(cmd, capture_output=True, text=True, timeout=120)


class TestThreadRoles:
    """The concurrency-contract checker (docs/static-analysis.md
    "Thread roles"): clean on the real tree with all three rules active,
    and every rule proven to fire on its negative fixture."""

    def test_clean_on_the_tree_with_all_rules(self):
        r = run_role_checker()
        assert r.returncode == 0, \
            f"thread-role contract drift:\n{r.stdout}{r.stderr}"
        for rule in ("ROLE-COVERAGE", "ROLE-CALL", "SIGNAL-SAFE"):
            assert rule in r.stderr, f"rule {rule} did not run: {r.stderr}"

    @pytest.mark.parametrize("name,expected", ROLE_FIXTURE_CASES,
                             ids=[c[0] for c in ROLE_FIXTURE_CASES])
    def test_fixture(self, name, expected):
        r = run_role_checker(os.path.join(FIXTURES, name))
        assert r.returncode == 1, \
            f"{name}: exit {r.returncode}, wanted 1:\n{r.stdout}"
        for rel, line, rule, frag in expected:
            want = f"{rel}:{line}: [{rule}]"
            hit = [l for l in r.stdout.splitlines()
                   if l.startswith(want) and frag in l]
            assert hit, (f"{name}: expected a finding '{want} ...{frag}...', "
                         f"got:\n{r.stdout}")
        assert len(r.stdout.strip().splitlines()) == len(expected), \
            f"{name}: unexpected extra findings:\n{r.stdout}"


class TestDeletionTripwires:
    """The acceptance contract in reverse: strip ONE annotation / ONE table
    entry from the real tree (copied aside) and the matching checker must go
    red. Guards against the rules rotting into always-green."""

    def _native_copy(self, tmp_path):
        dst = tmp_path / "horovod_tpu" / "native"
        dst.parent.mkdir(parents=True)
        shutil.copytree(NATIVE, dst,
                        ignore=shutil.ignore_patterns(
                            "*.o", "*.so", "build-*", "unit_tests"))
        return tmp_path

    def test_deleting_one_role_annotation_fails_the_checker(self, tmp_path):
        root = self._native_copy(tmp_path)
        hdr = root / "horovod_tpu" / "native" / "shm_transport.h"
        text = hdr.read_text()
        lines = text.splitlines(keepends=True)
        victim = next(i for i, l in enumerate(lines)
                      if "HVDTPU_CALLED_ON(" in l)
        del lines[victim]
        hdr.write_text("".join(lines))
        r = run_role_checker(str(root))
        assert r.returncode != 0, \
            "deleting an annotation must fail ROLE-COVERAGE"
        assert "[ROLE-COVERAGE]" in r.stdout and "shm_transport.h" in r.stdout

    def test_deleting_one_argtypes_entry_fails_the_linter(self, tmp_path):
        root = self._native_copy(tmp_path)
        src = os.path.join(REPO, "horovod_tpu", "basics.py")
        lines = open(src).read().splitlines(keepends=True)
        victim = next(i for i, l in enumerate(lines)
                      if '"hvdtpu_wire_stats"' in l)
        del lines[victim]
        (root / "horovod_tpu" / "basics.py").write_text("".join(lines))
        r = run_linter(str(root))
        assert r.returncode != 0, \
            "deleting a _C_API entry must fail ABI-MIRROR"
        assert "[ABI-MIRROR]" in r.stdout and "hvdtpu_wire_stats" in r.stdout


class TestClangTargets:
    """`make analyze` / `make tidy` must succeed whether or not clang is
    installed: with clang they are the real gates, without they print a
    SKIPPED notice and exit 0 (documented CI-only in
    docs/static-analysis.md)."""

    @pytest.mark.parametrize("target", ["analyze", "tidy"])
    def test_target_exits_zero(self, target):
        r = subprocess.run(["make", "-C", NATIVE, target],
                           capture_output=True, text=True, timeout=150)
        assert r.returncode == 0, \
            f"make {target} failed:\n{r.stdout}\n{r.stderr}"
        out = r.stdout + r.stderr
        import shutil
        tool = "clang++" if target == "analyze" else "clang-tidy"
        if shutil.which(tool) is None:
            assert "SKIPPED" in out, \
                f"make {target} without {tool} must say SKIPPED:\n{out}"


def _imported_names(path):
    """``(module, name)`` of everything a file imports, at module level or
    inside a function: ``import x`` gives ``(x, None)``, ``from x import y``
    ``(x, y)``, the module an absolute dotted name (relative ones resolved
    against the file's package; ``from . import y`` gives ``(<package>,
    y)``)."""
    package = os.path.relpath(os.path.dirname(path), REPO).split(os.sep)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            module = ".".join(base + ([node.module] if node.module else []))
            for alias in node.names:
                yield module, alias.name


def _imported_modules(path):
    """Every module a file imports, as an absolute dotted name (``from x
    import y`` counts as ``x`` and as ``x.y``)."""
    found = set()
    for module, name in _imported_names(path):
        found.add(module)
        if name is not None:
            found.add(f"{module}.{name}")
    return found


def _python_files(*parts):
    top = os.path.join(REPO, *parts)
    return [os.path.join(d, name) for d, _, names in os.walk(top)
            for name in names if name.endswith(".py")]


# The package's boxes from the floor up: every arrow between them points down.
LAYERS = ("ops", "compression", "parallel", "models")


class TestLayering:
    """``models/ -> parallel/ -> compression/ -> ops/``, and never the other
    way round: a mask change is made in ``ops/attention.py``, the kernels and
    one row of ``gpt._attention``, and nothing below ``models/`` has to
    follow it; whether a kernel compiles is ``ops/``'s to say, and
    ``compression/`` can shrink without a cell's kernels noticing."""

    @pytest.mark.parametrize("layer", LAYERS[:-1])
    def test_a_layer_imports_nothing_above_it(self, layer):
        """At module level or inside a function."""
        above = tuple(f"horovod_tpu.{name}"
                      for name in LAYERS[LAYERS.index(layer) + 1:])
        files = _python_files("horovod_tpu", layer)
        assert files, layer
        up = {os.path.relpath(f, REPO): sorted(
            m for m in _imported_modules(f)
            if m.startswith(above)) for f in files}
        assert not {f: m for f, m in up.items() if m}

    def test_no_kernel_module_is_built_from_anothers_private_names(self):
        """What two files of ``ops/`` share has a public name (the Pallas
        families': ``ops/pallas_util.py``): an edit to one file's ``_name``
        moves no other file's kernels."""
        taken = {os.path.relpath(f, REPO): sorted(
            f"{module}.{name}" for module, name in _imported_names(f)
            if module.startswith("horovod_tpu.") and name
            and name.startswith("_"))
            for f in _python_files("horovod_tpu", "ops")}
        assert not {f: names for f, names in taken.items() if names}

    def test_the_kernel_layer_says_itself_whether_a_kernel_compiles(
            self, monkeypatch):
        """On the CPU mesh the kernels run interpreted, and the answer comes
        from ``ops/`` alone: the call imports nothing of ``compression/``
        (which it asked until PR 43)."""
        import builtins
        from horovod_tpu.ops import pallas_util
        imported, real = [], builtins.__import__

        def spy(name, *args, **kwargs):
            imported.append(name)
            return real(name, *args, **kwargs)

        monkeypatch.setattr(builtins, "__import__", spy)
        interpret = pallas_util.use_interpret()
        monkeypatch.undo()
        assert interpret
        assert not [name for name in imported if "compression" in name]

    # The decoder's own arrows (``models/decoder``): what each file may
    # import of ``horovod_tpu.models``, by prefix. ``config`` nothing of the
    # package at all; a mixer, a feed-forward or the expert block neither
    # ``models/gpt.py`` nor another of them.
    DECODER = "horovod_tpu.models.decoder."
    DECODER_MAY_IMPORT = {
        "config.py": (), "parts.py": ("config",),
        "feed_forward.py": ("config", "parts"),
        "experts.py": ("config", "parts"),
        **{f"mixers/{name}.py": ("config", "parts") for name in (
            "attention", "cca", "diff_attention", "gdn", "gmu", "kda", "mla",
            "s6", "ssm")},
        "mixers/__init__.py": ("mixers",), "__init__.py": ()}

    @pytest.mark.parametrize("name", sorted(DECODER_MAY_IMPORT))
    def test_the_decoders_arrows_point_one_way(self, name):
        """``config <- parts <- mixers/, feed_forward, experts <-
        models/gpt.py``, at module level or inside a function."""
        path = os.path.join(REPO, "horovod_tpu", "models", "decoder", name)
        own = {m for m in _imported_modules(path)
               if m.startswith("horovod_tpu.models")
               and m != "horovod_tpu.models.decoder"}
        allowed = tuple(self.DECODER + part
                        for part in self.DECODER_MAY_IMPORT[name])
        assert not sorted(m for m in own if not m.startswith(allowed))
        if name == "config.py":
            assert not [m for m in _imported_modules(path)
                        if m.startswith("horovod_tpu")]

    def test_every_file_of_the_decoder_has_its_arrows_said(self):
        files = {os.path.relpath(f, os.path.join(
            REPO, "horovod_tpu", "models", "decoder"))
            for f in _python_files("horovod_tpu", "models", "decoder")}
        assert files == set(self.DECODER_MAY_IMPORT)

    def test_a_kept_name_is_declared_where_it_is_born(self):
        """Every literal a file under ``horovod_tpu/`` hands
        ``checkpoint_name`` is in that module's own ``SAVED_NAMES``
        (``models/gpt.py``: ``BLOCK_SAVED_NAMES``, beside the gathered
        tuple) and in no other's, a module declares no name it does not
        give, and ``gpt.SAVED_NAMES`` is their union: twenty-four names."""
        import importlib
        from horovod_tpu.models import gpt
        born = {}
        for path in _python_files("horovod_tpu"):
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            calls = [node for node in ast.walk(tree)
                     if isinstance(node, ast.Call) and "checkpoint_name" in (
                         getattr(node.func, "id", None),
                         getattr(node.func, "attr", None))]
            if calls:
                names = [call.args[1] for call in calls]
                assert all(isinstance(n, ast.Constant) for n in names), path
                born[os.path.relpath(path, REPO)] = {n.value for n in names}
        assert len(born) == 12, sorted(born)
        declared = {}
        for path in born:
            module = importlib.import_module(
                path[:-len(".py")].replace(os.sep, "."))
            declared[path] = set(
                module.BLOCK_SAVED_NAMES if module is gpt
                else module.SAVED_NAMES)
        assert declared == born
        everything = [name for names in born.values() for name in names]
        assert len(everything) == len(set(everything)) == 24
        assert len(gpt.SAVED_NAMES) == 24
        assert set(gpt.SAVED_NAMES) == set(everything)

    def test_the_attention_reference_stands_alone(self):
        """Pure ``jax.numpy``: nothing of this package, no flax."""
        path = os.path.join(REPO, "horovod_tpu", "ops", "attention.py")
        roots = {m.split(".")[0] for m in _imported_modules(path)}
        assert roots <= {"__future__", "jax", "numpy"}, roots
