"""jaxlint gate: every rule passes on every registered entry point, and every
rule has at least one fixture that fails it — so a rule that silently stops
firing breaks the suite, not just the invariant it guards."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import lint as lint_cli
from repro.analysis.ast_rules import AST_RULES, lint_source
from repro.analysis.findings import Finding, Report
from repro.analysis.jaxpr_rules import (
    JAXPR_RULES,
    rule_bounded_intermediate,
    rule_no_scatter_in_scan,
    rule_pinned_accumulator,
    rule_tile_shape,
    run_jaxpr_rules,
)
from repro.analysis.registry import (
    HOOK_MODULES,
    JaxprEntry,
    TileEntry,
    ast_targets,
    collect_entries,
)
from repro.core.tuning import TileConfig

S = jax.ShapeDtypeStruct


def _fatal(findings):
    return [f for f in findings if not f.suppressed]


# ----------------------- every rule x every entry ---------------------------


def test_registry_covers_the_serving_surface():
    names = {e.name for e in collect_entries()}
    expected = {
        "suco.query_streaming",
        "suco.query_fused",
        "suco.query_dense",
        "suco.engine_fused_bucket",
        "suco.engine_degraded_bucket",
        "suco.build_chunked",
        "sc_linear.query",
        "sc_linear.merge_pool_scan",
        "sc_linear.merge_pool_counting_scan",
        "sc_linear.merge_pool_with_dists_scan",
        "tuning.autotune_tiles",
        "kernels.sc_score.cells",
        "kernels.sc_score.cells_prefilter",
        "kernels.sc_score.cells_prefilter_compact",
        "kernels.sc_score.prefilter_compact_scan",
        "kernels.kmeans_assign.pair_hist",
        "kernels.sc_score.fused_distance",
        "kernels.sc_score.oracle",
        "kernels.gather_rerank.kernel",
        "kernels.gather_rerank.oracle",
        "kernels.kmeans_assign.batched",
        "kernels.kmeans_assign.stats",
        "kernels.kmeans_assign.oracle",
        "kernels.kmeans_assign.halves",
        "kernels.pairwise_l2.kernel",
        "kernels.pairwise_l2.oracle",
    }
    assert expected <= names, expected - names
    # targets under the AST engine
    tnames = {t.name for t in ast_targets()}
    assert "repro/serve/ann.py" in tnames
    assert any(t.startswith("repro/distributed/") for t in tnames)


def test_every_entry_passes_its_rules():
    """The acceptance gate: the whole registry lints clean (the in-process
    equivalent of `python -m repro.analysis.lint` exiting 0)."""
    for entry in collect_entries():
        findings, checked = run_jaxpr_rules(entry)
        assert checked, f"{entry.name}: no rules ran"
        assert _fatal(findings) == [], f"{entry.name}: {_fatal(findings)}"


def test_ast_engine_passes_on_serving_layer():
    for target in ast_targets():
        findings = lint_source(target.path.read_text(), target.name)
        assert _fatal(findings) == [], f"{target.name}: {_fatal(findings)}"


def test_sync_ok_annotations_are_audited():
    """The AsyncAnnServer retire point must stay an *annotated* sync — the
    suppression shows up in the report rather than vanishing."""
    target = next(t for t in ast_targets() if t.name == "repro/serve/ann.py")
    findings = lint_source(target.path.read_text(), target.name)
    suppressed = [f for f in findings if f.rule == "host-sync" and f.suppressed]
    assert suppressed, "expected annotated sync points in serve/ann.py"


# ------------------- failing fixtures: jaxpr rules --------------------------


def _entry(make, rules, **kw):
    return JaxprEntry(name="fixture", make=make, rules=rules, **kw)


def test_no_scatter_in_scan_fails_on_scatter_fixture():
    def bad(xs):
        def step(carry, row):
            return carry.at[0].set(row.sum()), None

        return jax.lax.scan(step, jnp.zeros(4), xs)[0]

    e = _entry(lambda: jax.make_jaxpr(bad)(jnp.ones((8, 16))), ("no-scatter-in-scan",))
    findings = rule_no_scatter_in_scan(e, e.make())
    assert findings and "scatter" in findings[0].message


def test_no_scatter_in_scan_fails_on_sort_fixture():
    def bad(xs):
        def step(carry, row):
            return carry + jnp.sort(row)[0], None

        return jax.lax.scan(step, jnp.float32(0), xs)[0]

    e = _entry(lambda: jax.make_jaxpr(bad)(jnp.ones((8, 16))), ("no-scatter-in-scan",))
    findings = rule_no_scatter_in_scan(e, e.make())
    assert findings and "sort" in findings[0].message


def test_no_scatter_in_scan_respects_scatter_budget():
    def small(xs):
        def step(carry, row):
            return carry.at[0].set(row.sum()), None

        return jax.lax.scan(step, jnp.zeros(4), xs)[0]

    e = _entry(
        lambda: jax.make_jaxpr(small)(jnp.ones((8, 16))),
        ("no-scatter-in-scan",),
        scatter_budget_elems=4,
    )
    assert rule_no_scatter_in_scan(e, e.make()) == []


def test_no_scatter_outside_scan_is_allowed():
    e = _entry(
        lambda: jax.make_jaxpr(lambda x: x.at[0].set(1.0))(jnp.ones(512)),
        ("no-scatter-in-scan",),
    )
    assert rule_no_scatter_in_scan(e, e.make()) == []


def test_bounded_intermediate_fails_on_tight_budget():
    e = _entry(
        lambda: jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.ones((64, 64)), jnp.ones((64, 64))
        ),
        ("bounded-intermediate",),
        budget_bytes=128,
    )
    findings = rule_bounded_intermediate(e, e.make())
    assert findings and "exceeds" in findings[0].message


def test_pinned_accumulator_fails_on_bf16_matmul():
    # jnp.sum upcasts bf16 inputs to an f32 accumulator on its own (and the
    # rule accepts that); the genuinely unsafe pattern is a contraction whose
    # preferred_element_type pins the accumulator to bf16.
    def bad(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16,
        )

    x = jnp.ones((8, 8), jnp.bfloat16)
    e = _entry(lambda: jax.make_jaxpr(bad)(x, x), ("pinned-accumulator",))
    findings = rule_pinned_accumulator(e, e.make())
    assert findings and "bfloat16" in findings[0].message


def test_pinned_accumulator_passes_on_upcast_bf16_sum_and_f32_matmul():
    for fn, arg in (
        # bf16 jnp.sum traces to convert->f32 reduce_sum: safe
        (lambda x: jnp.sum(x), jnp.ones((8, 8), jnp.bfloat16)),
        (lambda x: jnp.sum(x), jnp.ones((8, 8))),
        (lambda x: x @ x, jnp.ones((8, 8))),
    ):
        e = _entry(lambda: jax.make_jaxpr(fn)(arg), ("pinned-accumulator",))
        assert rule_pinned_accumulator(e, e.make()) == []


def test_dense_query_is_the_real_world_scatter_fixture():
    """The dense reference path (which deliberately does NOT declare
    no-scatter-in-scan) fails the rule — proof the rule bites on the real
    query stack, not only on synthetic jaxprs."""
    entries = {e.name: e for e in collect_entries(modules=("repro.core.suco",))}
    dense = entries["suco.query_dense"]
    assert "no-scatter-in-scan" not in dense.rules
    hypothetical = dataclasses.replace(dense, rules=("no-scatter-in-scan",))
    findings = rule_no_scatter_in_scan(hypothetical, hypothetical.make())
    assert findings, "dense path should scatter/sort inside its subspace scan"


# ------------------- failing fixtures: tile-shape ---------------------------


def _identity_pallas_jaxpr(block_cols: int, block_rows: int = 8):
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def run(x):
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, block_cols), lambda i: (i, 0)),
            out_shape=S((2 * block_rows, block_cols), jnp.float32),
            interpret=True,
        )(x)

    return jax.make_jaxpr(run)(jnp.ones((2 * block_rows, block_cols), jnp.float32))


def test_tile_shape_fails_on_bad_tile_config():
    e = TileEntry(
        name="fixture.tiles",
        contract={
            "sublane": 8,
            "lane": 128,
            "block_quantum": 512,
            "cap_quantum": 64,
        },
        tile_configs=(TileConfig(block_n=1000, bm=7, bn=100, survivor_cap=50),),
    )
    messages = [f.message for f in rule_tile_shape(e)]
    assert any("bm=7" in m for m in messages)
    assert any("bn=100" in m for m in messages)
    assert any("block_n=1000" in m for m in messages)
    assert any("survivor_cap=50" in m for m in messages)


def test_tile_shape_fails_on_misaligned_block():
    e = TileEntry(
        name="fixture.lane",
        contract={"lane": 128, "block_align": {0: ((1, 128),)}},
        make=lambda: _identity_pallas_jaxpr(64),
    )
    findings = rule_tile_shape(e)
    assert findings and "not a multiple of 128" in findings[0].message


def test_tile_shape_fails_on_vmem_overflow():
    e = TileEntry(
        name="fixture.vmem",
        contract={"vmem_bytes": 64, "double_buffer": 2},
        make=lambda: _identity_pallas_jaxpr(128),
    )
    findings = rule_tile_shape(e)
    assert findings and "VMEM budget" in findings[0].message


@pytest.mark.parametrize(
    "block_rows, block_cols, legal",
    [
        (8, 128, True),  # (8, 128)-divisible
        (8, 64, True),  # minor dim equals the operand's 64 columns
        (1, 128, False),  # one row of a 2-row operand: not 8-divisible
        (4, 256, False),
    ],
)
def test_tile_shape_enforces_mosaic_block_rule(block_rows, block_cols, legal):
    """The last two block dims must be (8, 128)-divisible or equal the
    operand's own dims — the rule the TPU compiler applies."""
    e = TileEntry(
        name="fixture.mosaic",
        contract={},
        make=lambda: _identity_pallas_jaxpr(block_cols, block_rows),
    )
    messages = [f.message for f in rule_tile_shape(e)]
    assert (not messages) if legal else any("(8, 128)" in m for m in messages)


def test_tile_shape_reads_squeezed_and_blocked_dims():
    """``BlockSpec`` dims arrive as ``Blocked``/``Squeezed`` objects: a
    squeezed leading dim counts as 1 and a squeezed second-minor dim is
    held to the tiling rule like any other."""

    def jaxpr(block, shape):
        def run(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: o_ref.__setitem__(Ellipsis, x_ref[...]),
                grid=(shape[0],),
                in_specs=[pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(shape) - 1))],
                out_specs=pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(shape) - 1)),
                out_shape=S(shape, jnp.float32),
                interpret=True,
            )(x)

        return jax.make_jaxpr(run)(jnp.ones(shape, jnp.float32))

    legal = TileEntry(
        name="fixture.squeezed3d",
        contract={"vmem_bytes": 2 * 4 * 8 * 128 * 2},
        make=lambda: jaxpr((None, 8, 128), (4, 8, 128)),
    )
    assert rule_tile_shape(legal) == []
    row = TileEntry(
        name="fixture.squeezed2d",
        contract={},
        make=lambda: jaxpr((None, 128), (4, 128)),
    )
    assert any("(8, 128)" in f.message for f in rule_tile_shape(row))


def test_tile_shape_fails_when_no_pallas_call_traced():
    e = TileEntry(
        name="fixture.nopallas",
        contract={},
        make=lambda: jax.make_jaxpr(lambda x: x + 1)(jnp.ones(8)),
    )
    findings = rule_tile_shape(e)
    assert findings and "no pallas_call" in findings[0].message


# ------------------- failing fixtures: AST rules ----------------------------


def test_host_sync_fails_on_unannotated_asarray():
    src = "import numpy as np\n\ndef f(x):\n    return np.asarray(x)\n"
    findings = _fatal(lint_source(src, "fixture.py"))
    assert [f.rule for f in findings] == ["host-sync"]


def test_host_sync_annotation_suppresses():
    src = "import numpy as np\n\ndef f(x):\n    return np.asarray(x)  # jaxlint: sync-ok\n"
    findings = lint_source(src, "fixture.py")
    assert findings and all(f.suppressed for f in findings)


def test_host_sync_ignores_host_literals():
    src = "import numpy as np\n\ndef f(a, b):\n    return np.asarray([a, b]), np.asarray([x * 2 for x in (a, b)])\n"
    assert lint_source(src, "fixture.py") == []


def test_host_sync_flags_block_until_ready_and_item():
    src = (
        "import jax\n\ndef f(x):\n"
        "    jax.block_until_ready(x)\n"
        "    return x.item()\n"
    )
    rules = [f.rule for f in _fatal(lint_source(src, "fixture.py"))]
    assert rules == ["host-sync", "host-sync"]


def test_host_sync_flags_unannotated_fsync():
    """ISSUE-10: a durability layer full of ``os.fsync`` must declare every
    one as deliberately off the serving path — an unannotated fsync is a
    lint error, same as a device sync."""
    src = "import os\n\ndef commit(f):\n    os.fsync(f.fileno())\n"
    findings = _fatal(lint_source(src, "fixture.py"))
    assert [f.rule for f in findings] == ["host-sync"]
    assert "fsync" in findings[0].message


def test_host_sync_fsync_annotation_suppresses():
    src = (
        "import os\n\ndef commit(f):\n"
        "    os.fsync(f.fileno())  # jaxlint: sync-ok — group commit\n"
    )
    findings = lint_source(src, "fixture.py")
    assert findings and all(f.suppressed for f in findings)


def test_host_sync_flags_bare_name_fsync():
    src = "from os import fsync\n\ndef commit(fd):\n    fsync(fd)\n"
    findings = _fatal(lint_source(src, "fixture.py"))
    assert [f.rule for f in findings] == ["host-sync"]


def test_tracer_branch_fails_on_if_over_traced_arg():
    src = (
        "import jax\n\n@jax.jit\ndef f(x, flag):\n"
        "    if flag:\n        return x + 1\n    return x\n"
    )
    findings = _fatal(lint_source(src, "fixture.py"))
    assert [f.rule for f in findings] == ["tracer-branch"]
    assert "flag" in findings[0].message


def test_tracer_branch_respects_static_argnames():
    src = (
        "import functools\nimport jax\n\n"
        "@functools.partial(jax.jit, static_argnames=('flag',))\n"
        "def f(x, flag):\n"
        "    if flag:\n        return x + 1\n    return x\n"
    )
    assert _fatal(lint_source(src, "fixture.py")) == []


def test_tracer_branch_disable_comment():
    src = (
        "import jax\n\n@jax.jit\ndef f(x, flag):\n"
        "    if flag:  # jaxlint: disable=tracer-branch\n"
        "        return x + 1\n    return x\n"
    )
    findings = lint_source(src, "fixture.py")
    assert findings and all(f.suppressed for f in findings)


def test_jit_in_hot_path_fails_inside_loop():
    src = (
        "import jax\n\ndef serve(batches):\n"
        "    out = []\n"
        "    for b in batches:\n"
        "        out.append(jax.jit(lambda x: x + 1)(b))\n"
        "    return out\n"
    )
    findings = _fatal(lint_source(src, "fixture.py"))
    assert [f.rule for f in findings] == ["jit-in-hot-path"]


def test_jit_outside_loop_is_fine():
    src = (
        "import jax\n\nf = jax.jit(lambda x: x + 1)\n\n"
        "def serve(batches):\n    return [f(b) for b in batches]\n"
    )
    assert _fatal(lint_source(src, "fixture.py")) == []


# -------------------------- suppressions & report ---------------------------


def test_entry_level_suppression_is_reported_not_fatal():
    def bad(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16,
        )

    x = jnp.ones((8, 8), jnp.bfloat16)
    e = _entry(
        lambda: jax.make_jaxpr(bad)(x, x),
        ("pinned-accumulator",),
        suppress={"pinned-accumulator": "fixture: bf16 on purpose"},
    )
    findings, checked = run_jaxpr_rules(e)
    assert checked == ["pinned-accumulator"]
    assert findings and all(f.suppressed for f in findings)
    assert findings[0].suppress_reason == "fixture: bf16 on purpose"


def test_report_json_shape():
    r = Report()
    r.mark_checked("host-sync", "a.py")
    r.extend(
        [
            Finding(rule="host-sync", target="a.py:3", message="boom"),
            Finding(
                rule="host-sync",
                target="a.py:9",
                message="ok",
                suppressed=True,
                suppress_reason="annotated",
            ),
        ]
    )
    payload = json.loads(r.to_json())
    assert payload["ok"] is False
    assert payload["n_findings"] == 1
    assert payload["n_suppressed"] == 1
    assert payload["checked"] == {"host-sync": ["a.py"]}
    assert not r.ok and len(r.fatal) == 1


def test_unknown_rule_name_is_a_finding():
    e = _entry(lambda: jax.make_jaxpr(lambda x: x + 1)(jnp.ones(4)), ("bogus-rule",))
    findings, checked = run_jaxpr_rules(e)
    assert checked == []
    assert findings and "unknown jaxpr rule" in findings[0].message


# -------------------------------- CLI ---------------------------------------


def test_cli_json_ast_only(capsys, tmp_path):
    out_path = tmp_path / "jaxlint.json"
    rc = lint_cli.main(
        ["--format", "json", "--rules", ",".join(AST_RULES), "--output", str(out_path)]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True
    assert set(AST_RULES) <= set(payload["checked"])
    assert json.loads(out_path.read_text()) == payload


def test_cli_list_and_unknown_rule(capsys):
    assert lint_cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in list(JAXPR_RULES) + ["tile-shape", *AST_RULES]:
        assert rule in out
    assert "suco.query_fused" in out
    assert lint_cli.main(["--rules", "nonexistent"]) == 2


def test_cli_disable_suppresses(capsys):
    rc = lint_cli.main(
        ["--format", "json", "--rules", "host-sync", "--disable", "host-sync"]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True


def test_hook_modules_all_export_entries():
    import importlib

    for mod in HOOK_MODULES:
        assert hasattr(importlib.import_module(mod), "jaxlint_entries"), mod
