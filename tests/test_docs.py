"""The documentation layer must not rot.

Three guards, all CI-enforceable without a human reading the docs:

  * the README quickstart commands are parsed out of the fenced code
    blocks and *executed* (shrunk onto a tiny synthetic corpus — same
    flags, smaller sizes), so a CLI change that breaks the documented
    invocation fails the docs lane;
  * the docs cross-link web (README <-> ARCHITECTURE <-> ROADMAP, the
    tier-1 verify command) is checked for presence;
  * every public module/class/function/method of the serving API keeps
    a docstring — an AST-level equivalent of the ruff D1xx rules that
    runs even where ruff isn't installed (the docs CI lane additionally
    runs the full ruff D-rule set).
"""

from __future__ import annotations

import ast
import pathlib
import re
import shlex

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
README = REPO / "README.md"
ARCHITECTURE = REPO / "docs" / "ARCHITECTURE.md"
ROADMAP = REPO / "ROADMAP.md"

# the modules whose public surface the docstring lint covers (kept in
# sync with the ruff invocation in .github/workflows/ci.yml)
DOCSTRING_SCOPE = [
    "src/repro/serving/__init__.py",
    "src/repro/serving/batching.py",
    "src/repro/serving/retrieval.py",
    "src/repro/serving/async_service.py",
    "src/repro/serving/state_cache.py",
    "src/repro/serving/scheduler.py",
    "src/repro/serving/qos.py",
    "src/repro/serving/delta.py",
    "src/repro/serving/decode.py",
    "src/repro/core/serving_plan.py",
    "src/repro/index/streaming.py",
    "src/repro/distributed/group_sharding.py",
    "src/repro/obs/__init__.py",
    "src/repro/obs/metrics.py",
    "src/repro/obs/trace.py",
    "src/repro/obs/profile.py",
    "src/repro/obs/recall.py",
    "src/repro/obs/health.py",
]

# quickstart smoke: same flags as documented, shrunk to a tiny corpus
TINY_OVERRIDES = {
    "--n": "512",
    "--d": "16",
    "--n-weights": "6",
    "--n-subset": "3",
    "--n-queries": "12",
    "--k": "3",
    "--v": "4",
    "--q-batch": "4",
    # the documented sharded invocation forces an 8-device mesh via
    # XLA_FLAGS; the in-process smoke keeps the single real device
    "--shards": "1",
}
_STORE_TRUE = {"--check", "--async", "--driver",
               "--prefetch", "--qos", "--health"}


def _fenced_blocks(text: str) -> list[str]:
    return re.findall(r"```(?:\w*)\n(.*?)```", text, flags=re.S)


def _extract_cli_commands(text: str) -> list[list[str]]:
    """Documented `repro.launch.retrieval` invocations -> argv lists."""
    cmds = []
    for block in _fenced_blocks(text):
        joined = block.replace("\\\n", " ")
        for line in joined.splitlines():
            if "repro.launch.retrieval" not in line:
                continue
            toks = shlex.split(line)
            argv = toks[toks.index("repro.launch.retrieval") + 1:]
            cmds.append(argv)
    return cmds


def _shrink(argv: list[str]) -> list[str]:
    """Re-emit a documented argv with tiny-corpus size overrides."""
    out, seen, i = [], set(), 0
    while i < len(argv):
        tok = argv[i]
        if tok in _STORE_TRUE:
            out.append(tok)
            i += 1
            continue
        val = argv[i + 1]
        seen.add(tok)
        out.extend([tok, TINY_OVERRIDES.get(tok, val)])
        i += 2
    for flag, val in TINY_OVERRIDES.items():
        if flag not in seen:
            out.extend([flag, val])
    return out


def test_readme_quickstart_commands_run():
    """Every documented launcher invocation must execute end to end (on a
    tiny synthetic corpus) and, when it documents --check, agree with the
    host oracle on every answer."""
    from repro.launch.retrieval import main

    cmds = _extract_cli_commands(README.read_text())
    assert len(cmds) >= 2, "README must document sync and async quickstarts"
    assert any("--async" in c for c in cmds)
    assert any("--async" not in c for c in cmds)
    for argv in cmds:
        out = main(_shrink(argv))
        assert out["n_check_failures"] == 0, f"quickstart failed: {argv}"


def test_readme_paging_flags_documented_and_valid():
    """The paging flags named in the README must parse in the launcher."""
    from repro.launch.retrieval import parse_args, parse_bytes

    text = README.read_text()
    assert "--max-resident-groups" in text
    assert "--device-budget" in text
    args = parse_args(["--max-resident-groups", "2",
                       "--device-budget", "512MB"])
    assert args.max_resident_groups == 2
    assert args.device_budget == 512 * 2**20
    assert parse_bytes("2GB") == 2 << 30
    with pytest.raises(Exception):
        parse_bytes("twelve parsecs")
    with pytest.raises(Exception):
        parse_bytes("0")  # floors to 0 bytes
    with pytest.raises(Exception):
        parse_bytes("0.5")  # fractional without unit: missing suffix
    with pytest.raises(Exception):
        parse_bytes("1.5")  # ditto — would silently mean 1 byte
    assert parse_bytes("1.5GB") == int(1.5 * (1 << 30))
    # case-insensitive + IEC suffixes, clear rejection of negatives
    assert parse_bytes("512mb") == 512 * 2**20
    assert parse_bytes("512MiB") == 512 * 2**20
    assert parse_bytes("2gib") == 2 << 30
    assert parse_bytes("1KiB") == 1024
    with pytest.raises(Exception, match="positive"):
        parse_bytes("-512MB")
    with pytest.raises(Exception, match="positive"):
        parse_bytes("0")
    with pytest.raises(Exception, match="unit"):
        parse_bytes("512XB")


def test_readme_documents_install_and_tier1_verify():
    text = README.read_text()
    assert "pip install -e .[test]" in text
    # the exact tier-1 command from ROADMAP.md, verbatim
    assert "PYTHONPATH=src python -m pytest -x -q" in text


def test_docs_cross_links():
    """README <-> ARCHITECTURE <-> ROADMAP must stay linked, and the
    architecture guide must keep covering the five layers + paging."""
    assert ARCHITECTURE.exists()
    readme = README.read_text()
    assert "docs/ARCHITECTURE.md" in readme
    roadmap = ROADMAP.read_text()
    assert "docs/ARCHITECTURE.md" in roadmap
    arch = ARCHITECTURE.read_text()
    assert "```mermaid" in arch
    for anchor in ("serving_plan.py", "QueryStepCache", "StateCache",
                   "batching.py", "RetrievalService",
                   "AsyncRetrievalService", "launch/retrieval.py",
                   "state_nbytes", "max_resident_groups",
                   "DeltaIndex", "delta_seal_rows", "append_to_state",
                   "n_valid", "ServiceDriver", "DeadlinePrefetch",
                   "CostAwareEviction", "scheduler.py", "prefetch",
                   "purge=True", "group_sharding.py", "serving_mesh",
                   "state_shardings", "strict=True",
                   "build_group_state_per_host",
                   "offload_state_sharded", "n_shards",
                   "qos.py", "QosScheduler", "QosClass",
                   "DeficitRoundRobin", "TokenBucket", "DegradeStep",
                   "degrade_ladder", "RateLimited", "capacity_per_tick",
                   "degrade_after",
                   "obs/metrics.py", "obs/trace.py", "obs/profile.py",
                   "MetricsRegistry", "TraceSpan", "Tracer", "Profiler",
                   "--trace-out", "--metrics-out", "--profile-dir",
                   "wlsh_group_queries_total", "wlsh_query_wait_seconds",
                   "tick_summary",
                   "obs/recall.py", "obs/health.py",
                   "RecallEstimator", "HealthMonitor", "AlertRule",
                   "sample_hash", "ShadowJob",
                   "--recall-sample-rate", "--alerts-out", "--health",
                   "wlsh_recall_observed", "wlsh_recall_bound_margin",
                   "benchmarks/sentinel.py", "BASELINE.json",
                   "BENCH_serve.json", "--write-baseline"):
        assert anchor in arch, f"ARCHITECTURE.md lost its {anchor} coverage"


def _missing_docstrings(path: pathlib.Path) -> list[str]:
    """AST D1xx sweep: public defs in ``path`` lacking a docstring."""
    tree = ast.parse(path.read_text())
    missing = []
    if ast.get_docstring(tree) is None:
        missing.append(f"{path.name}: module")

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
            ):
                name = child.name
                public = not name.startswith("_")
                if public and ast.get_docstring(child) is None:
                    missing.append(f"{path.name}: {prefix}{name}")
                if isinstance(child, ast.ClassDef) and public:
                    walk(child, f"{prefix}{name}.")

    walk(tree, "")
    return missing


@pytest.mark.parametrize("relpath", DOCSTRING_SCOPE)
def test_public_serving_api_has_docstrings(relpath):
    """Local equivalent of the docs-lane ruff D1xx rules: every public
    module/class/function/method in the serving API is documented."""
    missing = _missing_docstrings(REPO / relpath)
    assert not missing, "missing docstrings:\n  " + "\n  ".join(missing)
