"""Repo-invariant lint gate (ISSUE 8 satellite): the AST lint must be
clean on every commit.  See alpa_tpu/analysis/lint.py for the rule set
and docs/static_analysis.md for the rationale; run standalone with
``python scripts/verify_tool.py verify lint``."""
import fnmatch
import glob
import os
import re

from alpa_tpu.analysis import lint


def test_repo_lint_is_clean():
    violations = lint.run_lint()
    assert not violations, "\n" + lint.format_report(violations)


def test_lint_rules_actually_detect(tmp_path):
    """The gate must not pass vacuously: seed a scratch repo with one
    violation of each class and check every rule fires."""
    pkg = tmp_path / "alpa_tpu"
    pkg.mkdir()
    (tmp_path / "docs").mkdir()
    (pkg / "global_env.py").write_text(
        "import os\n"
        "class GlobalConfig:\n"
        "    def __init__(self):\n"
        "        self.undocumented_knob = True\n")
    (pkg / "bad.py").write_text(
        "from alpa_tpu.timer import tracer\n"
        "REG.counter('bad_metric_name', 'description')\n"
        "REG.gauge('alpa_scratch_gauge', 'well-named but undocumented')\n"
        "fault.fire('no_such_site')\n"
        "call_with_retry(f, site='also_missing')\n")
    (pkg / "badcodec.py").write_text(
        "def encode(x, mode):\n"
        "    return x\n"
        "\n"
        "def decode(q, s, shape, dtype, mode):\n"
        "    return q\n")
    (pkg / "analysis").mkdir()
    (pkg / "analysis" / "badfinding.py").write_text(
        "CODE = 'equiv.scratch-undocumented'\n")
    codes = {v.code for v in lint.run_lint(root=str(tmp_path))}
    assert codes >= {"config-env", "config-doc", "metric-name",
                     "metric-doc", "timer-import", "fault-site",
                     "codec-bound", "finding-code-doc"}, codes


def test_known_sites_registry_matches_docstring_table():
    """Every registered fault site must be documented in the fault.py
    docstring table (and the registry must cover the instrumented
    set the rest of the stack fires)."""
    import alpa_tpu.fault as fault
    for site in fault.KNOWN_SITES:
        assert f"``{site}``" in fault.__doc__, (
            f"site {site!r} missing from the fault.py docstring table")
    assert {"probe", "stage_launch", "cross_mesh_send",
            "cross_mesh_recv", "distributed_init"} <= fault.KNOWN_SITES


# ---------------------------------------------------------------------
# what the documents and the configuration name must exist (PR 28)
# ---------------------------------------------------------------------

ROOT = lint.repo_root()
_PROGRAM_DIRS = ("alpa_tpu", "chipbench", "examples", "scripts",
                 "benchmark")


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _program_text():
    """Every .py of the program and its tools but global_env.py."""
    chunks = [_read("chip_smoke.py")]
    for d in _PROGRAM_DIRS:
        for path in glob.glob(os.path.join(ROOT, d, "**", "*.py"),
                              recursive=True):
            if not path.endswith(os.path.join("alpa_tpu",
                                              "global_env.py")):
                chunks.append(_read(os.path.relpath(path, ROOT)))
    return "\n".join(chunks)


def test_every_global_config_field_has_a_reader():
    """A field nothing reads describes nothing: every field of
    ``global_config`` is read, as an attribute or by name, somewhere in
    the program or its tools outside ``global_env.py`` (tests do not
    count: a knob only a test sets is not a knob of the program)."""
    from alpa_tpu.global_env import global_config
    text = _program_text()
    unread = [f for f in vars(global_config)
              if not re.search(r"(\.\s*|[\"'])%s\b" % re.escape(f), text)]
    assert not unread, unread


def test_every_engine_option_has_a_caller():
    """An option nothing passes is a path only tests run: every keyword of
    ``ContinuousBatchingEngine.__init__`` is named by a call of the class
    under ``alpa_tpu/`` outside ``serve/engine.py``, as a keyword or as a
    key of a dict the call splats (``Controller.engine``'s ``rows``)."""
    import ast
    import inspect
    from alpa_tpu.serve.engine import ContinuousBatchingEngine
    named = set()
    for path in glob.glob(os.path.join(ROOT, "alpa_tpu", "**", "*.py"),
                          recursive=True):
        rel = os.path.relpath(path, ROOT)
        text = _read(rel)
        if rel == os.path.join("alpa_tpu", "serve", "engine.py") or \
                "ContinuousBatchingEngine(" not in text:
            continue
        tree = ast.parse(text)
        dicts = {}           # a name -> the keys of the dicts it is given
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                keys = {k.value for d in ast.walk(node.value)
                        if isinstance(d, ast.Dict) for k in d.keys
                        if isinstance(k, ast.Constant)}
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        dicts.setdefault(target.id, set()).update(keys)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and "ContinuousBatchingEngine" == \
                    getattr(node.func, "id", getattr(node.func, "attr", "")):
                for kw in node.keywords:
                    named |= {kw.arg} if kw.arg else \
                        dicts.get(getattr(kw.value, "id", None), set())
    options = [name for name, p in inspect.signature(
        ContinuousBatchingEngine.__init__).parameters.items()
        if p.default is not inspect.Parameter.empty]
    unnamed = [name for name in options if name not in named]
    assert not unnamed, unnamed


def _documents():
    """(name, text) of the documents a reader is sent to: README.md,
    PERF.md's sections 1 to 5 (6 and 7 are history and plans, and name
    what is gone) and docs/*.md."""
    yield "README.md", _read("README.md")
    perf = _read("PERF.md")
    yield "PERF.md", perf[perf.index("\n## 1. "):perf.index("\n## 6. ")]
    for path in sorted(glob.glob(os.path.join(ROOT, "docs", "*.md"))):
        rel = os.path.relpath(path, ROOT)
        yield rel, _read(rel)


def test_documents_name_only_defined_env_vars():
    defined = set(re.findall(r"ALPA_TPU_[A-Z0-9_]+",
                             _read("alpa_tpu/global_env.py")))
    # read by tests/tpu/, not a field of global_config
    defined.add("ALPA_TPU_TEST_ON_TPU")
    unknown = sorted({(name, var) for name, text in _documents()
                      for var in re.findall(r"ALPA_TPU_[A-Z0-9_]+", text)
                      if var not in defined})
    assert not unknown, unknown


def _named_paths(text):
    """Backticked tokens that name a file or directory of this repo: a
    path whose first segment is one of the repo's top-level directories,
    a top-level record (``PERF.md``, ``BENCHMARK.json``) or a ``.py``
    file named alone.  ``:line`` and ``::test`` suffixes are dropped,
    ``<...>`` stands for ``*``.  A token the text gives to another
    project (the word before it is ``Alpa``, ``Alpa's`` or ``upstream``)
    is a citation, not a path of this repo."""
    top_dirs = {d for d in os.listdir(ROOT)
                if os.path.isdir(os.path.join(ROOT, d)) and
                not d.startswith((".", "_"))}
    for m in re.finditer(r"`([^`\n]+)`", text):
        if re.search(r"\b(Alpa('s)?|upstream)\s+$", text[:m.start()]):
            continue
        tok = re.split(r"::|:\d", m.group(1).strip())[0].rstrip("/")
        if not re.fullmatch(r"[\w.\-/*<>]+", tok):
            continue
        if "/" in tok:
            if tok.split("/")[0] in top_dirs:
                yield tok
        elif re.fullmatch(r"[A-Z][A-Z0-9_]*\.(md|json|jsonl)|[\w\-]+\.py",
                          tok):
            yield tok


def test_documents_name_only_paths_that_exist():
    basenames = set()
    for _dir, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_")) and
                   d != "chiprun_out"]
        basenames.update(files)
    missing = set()
    for name, text in _documents():
        for tok in _named_paths(text):
            pattern = re.sub(r"<[^>]*>", "*", tok)
            found = glob.glob(os.path.join(ROOT, pattern)) or (
                "/" not in tok and fnmatch.filter(basenames, pattern))
            if not found:
                missing.add((name, tok))
    assert not missing, sorted(missing)


def test_imports_of_repo_modules_resolve():
    """Every ``import`` of one of the repo's own top-level packages, at
    module level or inside a function, names a module that exists (the
    documents' paths are checked above; a deleted module's importers
    are found here, whatever marks the test that reaches them)."""
    import ast
    own = {d for d in os.listdir(ROOT)
           if os.path.isfile(os.path.join(ROOT, d, "__init__.py"))}

    def resolves(dotted):
        base = os.path.join(ROOT, *dotted.split("."))
        return os.path.isfile(base + ".py") or os.path.isfile(
            os.path.join(base, "__init__.py"))

    def has_name(package, name):
        init = os.path.join(ROOT, *package.split("."), "__init__.py")
        return resolves(f"{package}.{name}") or not os.path.isfile(
            init) or re.search(r"\b%s\b" % re.escape(name), _read(
                os.path.relpath(init, ROOT)))

    missing = set()
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_")) and
                   d != "chiprun_out"]
        for fname in files:
            if not fname.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(top, fname), ROOT)
            for node in ast.walk(ast.parse(_read(rel), rel)):
                if isinstance(node, ast.Import):
                    mods = [(a.name, ()) for a in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    mods = [(node.module,
                             [a.name for a in node.names if a.name != "*"])]
                else:
                    continue
                for mod, names in mods:
                    if mod.split(".")[0] not in own:
                        continue
                    if not resolves(mod):
                        missing.add((rel, mod))
                        continue
                    missing.update((rel, f"{mod}.{n}") for n in names
                                   if not has_name(mod, n))
    assert not missing, sorted(missing)


def test_model_tests_go_through_the_harness():
    """A configuration's test file under ``tests/model/`` holds its
    configuration, its reference and its assertions; what every such file
    needs is in one place (``highest``, ``init_params``, ``jitted`` and
    ``shake`` in ``alpa_tpu/testing.py``, ``toy_context`` and
    ``catalog_row`` in ``tests/model/conftest.py``).  None defines one of
    its own, writes a ``run.Context(`` of its own, or calls a module's
    ``.init(`` (parameters are built under ``jit`` by ``init_params``;
    ``jax.eval_shape(model.init, ...)`` names the method and calls
    nothing)."""
    own = re.compile(
        r"^def (highest|init_params|jitted|shake|_?toy_context|catalog_row)\b"
        r"|run\.Context\(|\.init\(", re.M)
    found = []
    for path in sorted(glob.glob(os.path.join(ROOT, "tests", "model",
                                              "test_*.py"))):
        rel = os.path.relpath(path, ROOT)
        text = _read(rel)
        found += [(rel, text.count("\n", 0, m.start()) + 1, m.group())
                  for m in own.finditer(text)]
    assert not found, found
