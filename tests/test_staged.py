"""Staged-kernel contract checker tests (babble_tpu/analysis/staged.py,
docs/analysis.md "Kernel contracts").

One seeded-defect scratch-copy fixture per rule family — each appends a
defective staged function to a copy of the REAL kernel module and asserts
exactly its intended rule fires (the PR 8/17 pattern) — plus the standing
acceptance gates: the real tree at zero findings with the shipped (empty)
baseline, byte-identical finding streams across runs, every engine rung
carrying a checked contract, and the docs/tpu.md contract-table embed in
sync with the generator.
"""

from __future__ import annotations

import ast
import textwrap
from pathlib import Path

from babble_tpu.analysis.core import SourceFile
from babble_tpu.analysis.runner import main as lint_main, run_lint
from babble_tpu.analysis.staged import (
    check_staged,
    collect_contracts,
    kernel_baseline_entries,
    render_contract_table,
)

REPO_ROOT = str(Path(__file__).resolve().parents[1])

KERNELS = Path(REPO_ROOT) / "babble_tpu" / "tpu" / "kernels.py"
SHARDED = Path(REPO_ROOT) / "babble_tpu" / "tpu" / "sharded.py"


def _seed(tmp_path: Path, real: Path, extra: str) -> Path:
    """Scratch copy of a REAL tpu module with a seeded defect appended."""
    p = tmp_path / "babble_tpu" / "tpu" / real.name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(real.read_text() + textwrap.dedent(extra))
    return p


def _staged_lint(root) -> list:
    return run_lint(str(root), baseline_path=None, staged=True).new


# ---------------------------------------------------------------------------
# one seeded-defect fixture per rule family
# ---------------------------------------------------------------------------


def test_seeded_layout_mix_fires_exactly_its_rule(tmp_path):
    """A packed uint32 word table flowing into a traced select against the
    wide table it was packed from is the layout-mix hazard."""
    real_lines = len(KERNELS.read_text().splitlines())
    _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_layout_mix
        #   in: votes:bool[2]:wide
        #   rung: one-shot
        #   out: seeded
        @jax.jit
        def _seeded_layout_mix(votes):
            pv = pack_bits(votes)
            return jnp.where(votes, pv, votes)
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-layout-mix", "_seeded_layout_mix")
    ]
    assert found[0].line > real_lines


def test_seeded_donate_reuse_fires_exactly_its_rule(tmp_path):
    """Reading a buffer after donating it to a staged call is the
    use-after-donate hazard — XLA may have overwritten it in place."""
    _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_donated
        #   in: buf:i32[2]
        #   donate: buf
        #   rung: one-shot
        #   out: seeded
        @functools.partial(jax.jit, donate_argnums=(0,))
        def _seeded_donated(buf):
            return buf + 1


        def _seeded_driver(buf):
            out = _seeded_donated(buf)
            return out + buf.sum()
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-donate-reuse", "_seeded_driver")
    ]
    assert "donated to the staged call" in found[0].message


def test_seeded_wrong_psum_axis_fires_exactly_its_rule(tmp_path):
    """A collective naming an axis outside the contract's declared mesh
    axes is the dead-axis hazard."""
    _seed(tmp_path, SHARDED, """

        @functools.lru_cache(maxsize=2)
        def _seeded_mesh_factory(mesh, axis):
            # kernel-contract: _seeded_mesh_local
            #   in: x:i32[1]
            #   mesh: axis
            #   rung: sharded
            #   out: seeded
            def _seeded_mesh_local(x):
                return jax.lax.psum(x, "dead_axis")
            return jax.jit(_shard_map(
                _seeded_mesh_local, mesh=mesh, in_specs=(P(axis),),
                out_specs=P(axis),
            ))
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-mesh-axis", "_seeded_mesh_local")
    ]
    assert "dead_axis" in found[0].message


def test_seeded_retrace_hazard_fires_exactly_its_rule(tmp_path):
    """A shard_map factory without lru_cache re-traces per call — every
    invocation builds a fresh Python closure and fragments the
    executable cache."""
    _seed(tmp_path, SHARDED, """

        def _seeded_retrace_factory(mesh, axis):
            # kernel-contract: _seeded_retrace_local
            #   in: x:i32[1]
            #   mesh: axis
            #   rung: sharded
            #   out: seeded
            def _seeded_retrace_local(x):
                return x
            return jax.jit(_shard_map(
                _seeded_retrace_local, mesh=mesh, in_specs=(P(axis),),
                out_specs=P(axis),
            ))
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-retrace-hazard", "_seeded_retrace_local")
    ]
    assert "lru_cached" in found[0].message


def test_seeded_carry_drift_fires_exactly_its_rule(tmp_path):
    """A scan whose body returns a carry with a different abstract dtype
    than the init is the carry-drift hazard (XLA would reject it at trace
    time with an opaque error; the checker names the drifting slot)."""
    _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_carry
        #   in: x:i32[1]
        #   rung: one-shot
        #   out: seeded
        @jax.jit
        def _seeded_carry(x):
            def body(c, _):
                return c.astype(jnp.float32), None
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-carry-shape", "_seeded_carry")
    ]


# ---------------------------------------------------------------------------
# contract bookkeeping rules
# ---------------------------------------------------------------------------


def test_missing_contract_is_flagged(tmp_path):
    _seed(tmp_path, KERNELS, """

        @jax.jit
        def _seeded_uncontracted(x):
            return x + 1
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-contract", "_seeded_uncontracted")
    ]


def test_stale_contract_is_flagged(tmp_path):
    _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_gone
        #   in: x:i32[1]
        #   rung: one-shot
        #   out: stale
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.symbol) for f in found] == [
        ("kernel-contract", "_seeded_gone")
    ]
    assert "stale" in found[0].message


def test_kernel_ok_waiver_suppresses_and_is_audited(tmp_path):
    """kernel-ok on the offending line suppresses the finding; with
    --staged active an unconsumed kernel-ok is itself a dead waiver."""
    _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_waived
        #   in: votes:bool[2]:wide
        #   rung: one-shot
        #   out: seeded
        @jax.jit
        def _seeded_waived(votes):
            pv = pack_bits(votes)
            # kernel-ok: fixture proves waiver suppression
            return jnp.where(votes, pv, votes)
    """)
    assert _staged_lint(tmp_path) == []

    dead = _seed(tmp_path, KERNELS, """

        # kernel-contract: _seeded_clean
        #   in: x:i32[1]
        #   rung: one-shot
        #   out: seeded
        @jax.jit
        def _seeded_clean(x):
            # kernel-ok: nothing here needs waiving
            return x + 1
    """)
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.path) for f in found] == [
        ("lint-dead-waiver", "babble_tpu/tpu/kernels.py")
    ]
    assert dead.exists()


def test_contract_outside_staged_scope_is_dead_annotation(tmp_path):
    """A kernel-contract in a module the staged checker never analyzes
    can't be audited — under --staged it is flagged as dead."""
    p = tmp_path / "babble_tpu" / "node" / "fixture.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        # kernel-contract: nothing_here
        #   in: x:i32[1]
        def nothing_here(x):
            return x
    """))
    assert run_lint(str(tmp_path), baseline_path=None).new == []
    found = _staged_lint(tmp_path)
    assert [(f.rule, f.line) for f in found] == [("lint-dead-waiver", 1)]
    assert "outside the staged-analysis scope" in found[0].message


# ---------------------------------------------------------------------------
# acceptance gates: real tree clean, deterministic, rungs covered
# ---------------------------------------------------------------------------


def test_real_tree_zero_findings_with_empty_baseline():
    result = run_lint(REPO_ROOT, baseline_path=None, staged=True)
    assert result.errors == []
    assert [f.location() for f in result.new] == []
    assert kernel_baseline_entries() == []


def test_two_runs_emit_byte_identical_finding_streams(tmp_path):
    """Determinism of the finding stream itself, on a tree that actually
    produces findings (a clean tree is trivially identical)."""
    from babble_tpu.analysis.runner import format_report

    _seed(tmp_path, KERNELS, """

        @jax.jit
        def _seeded_uncontracted(x):
            return x + 1
    """)
    first = format_report(run_lint(str(tmp_path), baseline_path=None,
                                   staged=True))
    second = format_report(run_lint(str(tmp_path), baseline_path=None,
                                    staged=True))
    assert first.encode() == second.encode()


def test_every_engine_rung_carries_checked_contracts():
    """One-shot, frontier, doubling, sharded, incremental and the live
    serve path each declare contracts; the queued-dispatch rung stages
    the sharded/doubling kernels (tpu/dispatch.py holds no staged defs of
    its own — docs/tpu.md 'Kernel contracts'). Both voting layouts are
    covered: the sharded fame loop declares dual (wide+packed) carries
    and every fame kernel declares the `packed` layout static."""
    rows = collect_contracts(REPO_ROOT)
    rungs = {c.rung for _rel, _rec, c in rows}
    assert {"one-shot", "frontier", "doubling", "sharded",
            "incremental", "live"} <= rungs
    by_name = {rec.name: c for _rel, rec, c in rows}
    assert len(by_name) == 19
    duals = {
        name for name, c in by_name.items()
        if any(v.layout == "dual" for v in c.args.values())
    }
    assert "local_fame" in duals
    packed_statics = {
        name for name, c in by_name.items() if "packed" in c.statics
    }
    assert {"consensus_pipeline", "frontier_pipeline", "_fame_received",
            "_step_full", "multi_step"} <= packed_statics
    donated = {name for name, c in by_name.items() if c.donate}
    assert {"local_fame", "local_received", "_step_full",
            "multi_step"} <= donated


def _package_modules():
    """{relative path: parsed module} of the package, without the two
    places that only describe the contract surface."""
    pkg = Path(REPO_ROOT) / "babble_tpu"
    out = {}
    for path in sorted(pkg.rglob("*.py")):
        rel = path.relative_to(REPO_ROOT).as_posix()
        if (rel == "babble_tpu/obs/devledger.py"
                or rel.startswith("babble_tpu/analysis/")):
            continue
        out[rel] = ast.parse(path.read_text())
    return out


def _names_taken_from(module: str, others) -> set:
    """Names that other modules import from `module` (its basename, as in
    `from .incremental import step`) or read off it (`kernels.x`)."""
    taken = set()
    for tree in others:
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.split(".")[-1] == module):
                taken.update(a.name for a in node.names)
            elif (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == module):
                taken.add(node.attr)
    return taken


def _reached(tree: ast.Module, taken: set) -> set:
    """Top-level names of a module that code outside it can arrive at:
    those `taken` by other modules, those a bare module-level statement
    reads, and, from there on, whatever a reached definition reads."""
    defs, reached = {}, set(taken)
    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.ClassDef)):
            bound = [stmt.name]
        elif isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            bound = [t.id for t in targets if isinstance(t, ast.Name)]
        else:
            bound = []
        # what the statement reads, and the functions nested in it (a
        # shard_map body lives inside its factory)
        holds = {n.id for n in ast.walk(stmt) if isinstance(n, ast.Name)}
        holds |= {n.name for n in ast.walk(stmt)
                  if isinstance(n, ast.FunctionDef)}
        if not bound:
            reached |= holds
        for name in bound:
            defs[name] = holds - {name}
    frontier = [n for n in reached if n in defs]
    while frontier:
        new = defs[frontier.pop()] - reached
        reached |= new
        frontier += [n for n in new if n in defs]
    return reached


def test_every_contract_entry_is_reached_by_package_code():
    """A `# kernel-contract:` entry is a program some module of the
    package can launch: another module imports it (or the wrapper that
    binds it, or the factory that holds it), or a definition that is so
    reached calls it. Its own definition, the ledger's table
    (obs/devledger.py) and the checker (analysis/) do not count: an
    engine that only tests and root scripts call has no contract here."""
    modules = _package_modules()
    reached = {}  # per module that holds a contract
    unreached = []
    for rel, rec, _c in collect_contracts(REPO_ROOT):
        if rel not in reached:
            others = [t for r, t in modules.items() if r != rel]
            reached[rel] = _reached(
                modules[rel], _names_taken_from(Path(rel).stem, others))
        if rec.name not in reached[rel]:
            unreached.append(f"{rel}:{rec.name}")
    assert unreached == []


def test_contract_table_embed_in_sync_with_docs():
    table = render_contract_table(REPO_ROOT)
    doc = (Path(REPO_ROOT) / "docs" / "tpu.md").read_text()
    begin, end = "<!-- contract-table:begin -->", "<!-- contract-table:end -->"
    assert begin in doc and end in doc
    embedded = doc.split(begin, 1)[1].split(end, 1)[0].strip()
    assert embedded == table.strip(), (
        "docs/tpu.md contract table is stale — regenerate with "
        "`babble-tpu lint --contract-table`"
    )


def test_cli_staged_flag_and_contract_table(capsys):
    assert lint_main(["--staged", "--no-baseline"], root=REPO_ROOT) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out
    assert "lint wall-time:" in out
    assert "staged-kernel contracts included" in out

    assert lint_main(["--contract-table"], root=REPO_ROOT) == 0
    out = capsys.readouterr().out
    assert "| rung | staged function |" in out
    assert "local_fame" in out


def test_kernel_baseline_entries_filters_kernel_rules(tmp_path):
    import json

    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [
        {"rule": "det-wallclock", "path": "a.py", "symbol": "f", "text": "x"},
        {"rule": "kernel-layout-mix", "path": "b.py", "symbol": "g",
         "text": "y"},
    ]}))
    entries = kernel_baseline_entries(str(bl))
    assert [e["rule"] for e in entries] == ["kernel-layout-mix"]


def test_checker_consumes_real_contract_lines():
    """Every contract directive line in the real sharded module is marked
    used by the checker (none would survive the dead-annotation audit)."""
    sf = SourceFile.parse(str(SHARDED), "babble_tpu/tpu/sharded.py")
    findings = list(check_staged(sf))
    assert findings == []
    contract_lines = [
        ln for ln, text in sf.comments.items()
        if text.startswith("kernel-contract:") or any(
            text.startswith(d)
            for d in ("in:", "static:", "donate:", "mesh:", "rung:", "out:")
        )
    ]
    assert contract_lines
    assert set(contract_lines) <= sf.used_waiver_lines


def test_packed_surfaces_refuse_on_stale_kernel_baseline(monkeypatch, capsys):
    """bench_mesh_scale --headline packed and scripts/packed_smoke.py must
    refuse (clear error, exit 2) while the lint baseline carries any
    kernel-* entry: a packed headline over unproven kernels is a green
    number on unchecked code (ISSUE 18 bugfix)."""
    import importlib.util

    from babble_tpu.analysis import staged as staged_mod

    fake = [{"rule": "kernel-layout-mix",
             "path": "babble_tpu/tpu/kernels.py",
             "symbol": "consensus_pipeline", "text": "x"}]
    monkeypatch.setattr(
        staged_mod, "kernel_baseline_entries", lambda *a, **k: fake)

    spec = importlib.util.spec_from_file_location(
        "bench_mesh_scale_guard",
        str(Path(REPO_ROOT) / "bench_mesh_scale.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.main(["--headline", "packed", "--validators", "8"]) == 2
    err = capsys.readouterr().err
    assert "REFUSING" in err and "kernel-layout-mix" in err

    spec = importlib.util.spec_from_file_location(
        "packed_smoke_guard",
        str(Path(REPO_ROOT) / "scripts" / "packed_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main() == 2
    err = capsys.readouterr().err
    assert "REFUSING" in err and "lint --staged" in err
