"""Docs lint (grep-enforced, in the spirit of the PR 1 compat grep test):
code references in README / EXPERIMENTS / ARCHITECTURE must name real
files, modules and CLI flags, so the docs can't rot silently when code
moves.  Scope is deliberately narrow — repo-relative paths, dotted
``repro.*`` references, and ``--flag`` tokens; prose is untouched."""
import os
import re

import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
DOCS = ["README.md", "EXPERIMENTS.md", "ARCHITECTURE.md", "TUNING.md"]

PATH_RE = re.compile(
    r"\b(?:src|tests|benchmarks|examples)/[\w/.-]+\.(?:py|md|json|txt)\b")
MOD_RE = re.compile(r"\brepro(?:\.[A-Za-z_]\w*)+")
FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9_-]*\b")

# flags that are real but not argparse-declared in this repo
FLAG_ALLOW = {"--xla_force_host_platform_device_count"}


def _read(name: str) -> str:
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def _module_ref_ok(ref: str) -> bool:
    """Resolve ``repro.a.b[.attr]``: the longest dotted prefix must be a
    module file / package dir under src/, and the next component (if any)
    must appear as a word in that module (def/class/assignment/import —
    a plain grep keeps this robust to how the name is bound)."""
    parts = ref.split(".")
    for k in range(len(parts), 0, -1):
        base = os.path.join(ROOT, "src", *parts[:k])
        mod_file = None
        if os.path.isdir(base):
            if k == len(parts):
                return True
            mod_file = os.path.join(base, "__init__.py")
        elif os.path.isfile(base + ".py"):
            if k == len(parts):
                return True
            mod_file = base + ".py"
        if mod_file is not None:
            if not os.path.isfile(mod_file):
                return False
            return re.search(r"\b%s\b" % re.escape(parts[k]),
                             _read(os.path.relpath(mod_file, ROOT))) \
                is not None
    return False


def _declared_flags() -> set:
    flags = set()
    for top in ("src", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            for f in files:
                if f.endswith(".py"):
                    text = _read(os.path.relpath(
                        os.path.join(dirpath, f), ROOT))
                    flags |= set(re.findall(
                        r"add_argument\(\s*[\"'](--[\w-]+)", text))
    # the chip smoke run is an entry point at the repo root
    flags |= set(re.findall(r"add_argument\(\s*[\"'](--[\w-]+)",
                            _read("chip_smoke.py")))
    return flags


@pytest.mark.parametrize("doc", DOCS)
def test_doc_exists(doc):
    assert os.path.isfile(os.path.join(ROOT, doc)), f"{doc} missing"


@pytest.mark.parametrize("doc", DOCS)
def test_doc_paths_exist(doc):
    bad = [p for p in sorted(set(PATH_RE.findall(_read(doc))))
           if not os.path.exists(os.path.join(ROOT, p))]
    assert not bad, f"{doc} references missing files: {bad}"


@pytest.mark.parametrize("doc", DOCS)
def test_doc_module_refs_resolve(doc):
    bad = [m for m in sorted(set(MOD_RE.findall(_read(doc))))
           if not _module_ref_ok(m)]
    assert not bad, f"{doc} references unresolvable modules/attrs: {bad}"


@pytest.mark.parametrize("doc", DOCS)
def test_doc_flags_are_declared(doc):
    declared = _declared_flags() | FLAG_ALLOW
    bad = [f for f in sorted(set(FLAG_RE.findall(_read(doc))))
           if f not in declared]
    assert not bad, f"{doc} references undeclared CLI flags: {bad}"


def test_readme_links_architecture():
    assert "ARCHITECTURE.md" in _read("README.md"), \
        "README must link the architecture doc"


def test_docs_link_tuning_book():
    """The tuning chapter is part of the docs book: README and
    ARCHITECTURE must link it."""
    assert "TUNING.md" in _read("README.md")
    assert "TUNING.md" in _read("ARCHITECTURE.md")


def test_tuning_doc_covers_cache_contract():
    """TUNING.md must document the pieces users actually need: the cache
    env var / default location, the --retune escape hatch, and the
    calibration + selection entry points."""
    text = _read("TUNING.md")
    for needle in ("REPRO_PLAN_CACHE", ".cache/repro/plans", "--retune",
                   "repro.core.autotune", "--dp-degrees"):
        assert needle in text, f"TUNING.md must mention {needle}"


def test_wire_flag_declared_and_documented():
    """The --wire knob is argparse-declared (so the flag lint accepts the
    docs' mentions of it) and the tuning/architecture chapters cover the
    wire formats: encode attach points, per-wire plan caching, and the
    degree re-ranking it exists for."""
    assert "--wire" in _declared_flags()
    for doc, needles in (
            ("TUNING.md", ("--wire", "delta+bf16", "re-rank")),
            ("ARCHITECTURE.md", ("--wire", "repro.kernels.wirecodec",
                                 "RA207"))):
        text = _read(doc)
        for needle in needles:
            assert needle in text, f"{doc} must mention {needle}"


def test_overlap_flag_declared_and_documented():
    """The overlap knobs are argparse-declared and the docs book covers
    the schedules: the bucketed stage-major sync + double-buffered engine
    section in ARCHITECTURE, and the overlap-aware re-ranking (with its
    direction-flip caveat and per-budget plan caching) in TUNING."""
    declared = _declared_flags()
    assert "--sync-overlap" in declared
    assert "--sync-bucket-kb" in declared
    for doc, needles in (
            ("ARCHITECTURE.md", ("Overlap & scheduling", "--sync-overlap",
                                 "plan_grad_buckets", "stage-major",
                                 "audit_overlap_sync", "reduce_up_on_device",
                                 "tests/test_overlap.py")),
            ("TUNING.md", ("overlap_compute_s", "overlap_bucket",
                           "rate_optimal_s", "--sync-overlap",
                           "modeled_overlap_time"))):
        text = _read(doc)
        for needle in needles:
            assert needle in text, f"{doc} must mention {needle}"


def test_train_help_mentions_auto_and_engine():
    """The launcher's user-facing text must match reality: --dp-degrees
    documents the calibrated+cached 'auto' default (not the stale 'single
    round-robin stage'), --retune exists, and the module docstring points
    iterative graph workloads at the engine entry point."""
    text = _read("src/repro/launch/train.py")
    assert "repro.core.topology.tune" in text
    assert "repro.core.autotune" in text
    assert "repro.graph.engine" in text
    assert "default: single round-robin stage" not in text
    assert '"--retune"' in text
    assert "TUNING.md" in text
    for needle in ("calibrat", "cache"):
        assert needle in text, f"--dp-degrees help must mention {needle}"

def test_serve_flags_declared_and_documented():
    """The serving-tier knobs are argparse-declared (so the flag lint
    accepts the docs' mentions) and the docs book covers the tier: the
    dataflow + consistency-oracle section in ARCHITECTURE, the quickstart
    in README, and the bench → figure row in EXPERIMENTS."""
    declared = _declared_flags()
    for flag in ("--slots", "--rate", "--burst", "--queue-cap",
                 "--slo-steps", "--breach-window", "--cooldown-steps",
                 "--sparse-dispatch", "--head-size"):
        assert flag in declared, f"{flag} not argparse-declared"
    for doc, needles in (
            ("ARCHITECTURE.md", ("Serving tier", "--sparse-dispatch",
                                 "audit_serve_decode", "shape_bucket",
                                 "tests/test_serve_tier.py",
                                 "tests/test_admission.py",
                                 "repro.serve.scheduler",
                                 "repro.serve.dispatch")),
            ("README.md", ("repro.serve", "--sparse-dispatch",
                           "tests/test_serve_tier.py")),
            ("EXPERIMENTS.md", ("benchmarks/bench_serve.py",
                                "BENCH_pr10.json", "plan-cache hit rate"))):
        text = _read(doc)
        for needle in needles:
            assert needle in text, f"{doc} must mention {needle}"


# The public-docstring ast lint moved onto the rule engine: RA401 in
# repro.analysis.rules, enforced repo-wide by tests/test_analysis.py.
