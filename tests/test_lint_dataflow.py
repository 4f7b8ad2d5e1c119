"""gtdev device-contract verifier: dataflow engine + GT023-GT027.

Three layers under test:

1. the abstract-interpretation engine itself (``tools/lint/dataflow``):
   CFG joins, loop re-entry convergence, and the top-element
   conservatism contract (unknown facts must never manufacture
   findings);
2. the five device-contract rules, each with a positive fixture that
   must fire at a known line and a negative twin that must stay
   silent;
3. the ``--explain`` surface: every registered rule's shipped examples
   are linted for real (positive fires, negative is clean), so the
   docs cannot rot.
"""

from __future__ import annotations

import ast
import io
import textwrap

import pytest

from greptimedb_tpu.tools.lint import dataflow
from greptimedb_tpu.tools.lint.core import all_rules
from greptimedb_tpu.tools.lint.runner import explain_rule, lint_source

DATAFLOW_RULES = {"GT023", "GT024", "GT025", "GT026", "GT027"}


def run_lint(src: str, select=None):
    sel = {select} if isinstance(select, str) else select
    act, sup = lint_source("greptimedb_tpu/fixture.py",
                           textwrap.dedent(src), select=sel)
    return act, sup


def rules_hit(src: str, select=None):
    act, _ = run_lint(src, select)
    return [(f.rule, f.line) for f in act]


def analyze(src: str):
    tree = ast.parse(textwrap.dedent(src))
    return tree, dataflow.FileAnalyses(tree)


def value_of_return(tree, analyses, func_name: str) -> dataflow.AV:
    """AV of the expression returned by `func_name`."""
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == func_name:
            scope = analyses.scope(node)
            for n in ast.walk(node):
                if isinstance(n, ast.Return) and n.value is not None:
                    return scope.value(n.value)
    raise AssertionError(f"no return found in {func_name}")


# ---------------------------------------------------------------------------
# engine: CFG joins
# ---------------------------------------------------------------------------

def test_join_if_else_degrades_disagreeing_dims():
    tree, an = analyze("""
        import jax.numpy as jnp

        def f(flag):
            if flag:
                x = jnp.zeros((8, 128), jnp.float32)
            else:
                x = jnp.zeros((16, 128), jnp.float32)
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.kind == "array"
    # first dim disagrees across the branches -> unknown; the agreeing
    # lane dim and dtype survive the join
    assert av.shape == (None, 128)
    assert av.dtype == "float32"


def test_join_if_else_keeps_agreeing_facts():
    tree, an = analyze("""
        import jax.numpy as jnp

        def f(flag):
            if flag:
                x = jnp.zeros((8, 128), jnp.float32)
            else:
                x = jnp.ones((8, 128), jnp.float32)
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.kind == "array"
    assert av.shape == (8, 128)
    assert av.dtype == "float32"


def test_join_branch_without_assignment_degrades():
    # one path leaves x as the argument (top): the join must not
    # pretend the zeros facts hold unconditionally
    tree, an = analyze("""
        import jax.numpy as jnp

        def f(x, flag):
            if flag:
                x = jnp.zeros((8, 128), jnp.float32)
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.shape is None or None in (av.shape or (None,))


# ---------------------------------------------------------------------------
# engine: loop re-entry convergence
# ---------------------------------------------------------------------------

def test_loop_reentry_widens_and_terminates():
    # total takes 0, 1, 2, ... around the back edge; the fixpoint must
    # converge (finite lattice / visit cap) and must NOT report a
    # single concrete value
    tree, an = analyze("""
        def f(n):
            total = 0
            for i in range(n):
                total = total + 1
            return total
    """)
    av = value_of_return(tree, an, "f")
    assert av.kind in ("int", "top")
    assert av.value is None


def test_loop_invariant_array_facts_survive():
    tree, an = analyze("""
        import jax.numpy as jnp

        def f(n):
            x = jnp.zeros((8, 128), jnp.float32)
            for i in range(n):
                x = x + 1.0
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.kind == "array"
    assert av.shape == (8, 128)
    assert av.dtype == "float32"


def test_while_loop_terminates():
    tree, an = analyze("""
        def f(n):
            x = 1
            while x < n:
                x = x * 2
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.kind in ("int", "top")


# ---------------------------------------------------------------------------
# engine: constants and module scan
# ---------------------------------------------------------------------------

def test_fold_blocks_pin_matches_mesh():
    """KNOWN_CONSTANTS seeds FOLD_BLOCKS for the divisibility rule;
    a drift from the real mesh constant would silently rot GT025."""
    from greptimedb_tpu.parallel import mesh

    assert dataflow.KNOWN_CONSTANTS["FOLD_BLOCKS"] == mesh.FOLD_BLOCKS


def test_module_constant_feeds_function_scope():
    tree, an = analyze("""
        import jax.numpy as jnp

        ROWS = 16

        def f():
            x = jnp.zeros((ROWS, 128), jnp.bfloat16)
            return x
    """)
    av = value_of_return(tree, an, "f")
    assert av.shape == (16, 128)
    assert av.dtype == "bfloat16"


# ---------------------------------------------------------------------------
# engine: top-element conservatism — unknown facts stay silent
# ---------------------------------------------------------------------------

def test_unknown_shapes_produce_no_device_findings():
    # every geometric fact flows from arguments: the verifier knows
    # nothing and must say nothing
    assert rules_hit("""
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x, blk, interpret):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec(blk, lambda i: (i, 0))],
                out_specs=pl.BlockSpec(blk, lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=interpret,
            )(x)
    """, DATAFLOW_RULES) == []


def test_unknown_dtype_produces_no_promotion_findings():
    assert rules_hit("""
        import jax

        @jax.jit
        def f(x, y):
            return x + y
    """, DATAFLOW_RULES) == []


# ---------------------------------------------------------------------------
# GT023 BlockSpec tiling
# ---------------------------------------------------------------------------

GT023_POS = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def run(interpret):
        x = jnp.zeros((256, 192), jnp.float32)
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((128, 96), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((128, 96), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 192), jnp.float32),
            interpret=interpret,
        )(x)
"""


def test_gt023_positive_misaligned_lane_dim():
    hits = rules_hit(GT023_POS, "GT023")
    # both the in_spec and the out_spec carry the 96-lane block
    assert [h[0] for h in hits] == ["GT023", "GT023"]
    assert hits[0][1] in (13, 14)   # anchored at the in_spec BlockSpec


def test_gt023_positive_sublane_misalignment():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((30, 128), jnp.bfloat16)
            return pl.pallas_call(
                kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((15, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((15, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((30, 128), jnp.bfloat16),
                interpret=interpret,
            )(x)
    """, "GT023")
    # bf16 sublane is 16: a 15-row block needs relayout on every step
    assert [h[0] for h in hits] == ["GT023", "GT023"]


def test_gt023_negative_aligned_and_whole_array():
    assert rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((256, 256), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((128, 256), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 256), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT023") == []
    # a block spanning the WHOLE (known) trailing dim is exempt even
    # when that dim is not a multiple of 128 (Mosaic pads once)
    assert rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((256, 96), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((128, 96), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 96), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 96), jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT023") == []


# ---------------------------------------------------------------------------
# GT024 static VMEM overcommit
# ---------------------------------------------------------------------------

def test_gt024_positive_scratch_overcommit():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc):
            o_ref[...] = x_ref[...]

        def run(x, interpret):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                scratch_shapes=[pltpu.VMEM((4096, 4096), jnp.float32)],
                interpret=interpret,
            )(x)
    """, "GT024")
    # 4096*4096*f32 = 64 MiB of scratch alone vs the ~16 MiB core
    assert [h[0] for h in hits] == ["GT024"]


def test_gt024_positive_whole_array_residency():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((8192, 1024), jnp.float32)
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((8192, 1024),
                                               jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT024")
    # no grid: input + output resident whole, 2 * 32 MiB
    assert [h[0] for h in hits] == ["GT024"]


def test_gt024_negative_blocked_and_small():
    assert rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((8192, 1024), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(64,),
                in_specs=[pl.BlockSpec((128, 1024), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 1024), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((8192, 1024),
                                               jnp.float32),
                scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)],
                interpret=interpret,
            )(x)
    """, "GT024") == []


# ---------------------------------------------------------------------------
# GT025 grid x block divisibility
# ---------------------------------------------------------------------------

def test_gt025_positive_indivisible_rows():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            x = jnp.zeros((96, 128), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((96, 128), jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT025")
    # 96 rows cannot be covered by 64-row blocks without a ragged tail
    assert [h[0] for h in hits] == ["GT025", "GT025"]


def test_gt025_positive_fold_blocks_contract():
    # FOLD_BLOCKS is pinned in KNOWN_CONSTANTS: a shape built from it
    # resolves statically, so raggedness against it is detectable
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from greptimedb_tpu.parallel.mesh import FOLD_BLOCKS

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            rows = FOLD_BLOCKS * 100
            x = jnp.zeros((rows, 128), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(3,),
                in_specs=[pl.BlockSpec((96, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((96, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((rows, 128),
                                               jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT025")
    # 8 * 100 = 800 rows; 800 % 96 != 0
    assert [h[0] for h in hits] == ["GT025", "GT025"]


def test_gt025_negative_divisible():
    assert rules_hit("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from greptimedb_tpu.parallel.mesh import FOLD_BLOCKS

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(interpret):
            rows = FOLD_BLOCKS * 96
            x = jnp.zeros((rows, 128), jnp.float32)
            return pl.pallas_call(
                kernel,
                grid=(FOLD_BLOCKS,),
                in_specs=[pl.BlockSpec((96, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((96, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((rows, 128),
                                               jnp.float32),
                interpret=interpret,
            )(x)
    """, "GT025") == []


# ---------------------------------------------------------------------------
# GT026 dtype promotion in device scope
# ---------------------------------------------------------------------------

def test_gt026_positive_astype_wide():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            a = jnp.zeros((8, 128), jnp.float32)
            return a.astype(jnp.float64)
    """, "GT026")
    assert [h[0] for h in hits] == ["GT026"]
    assert hits[0][1] == 8


def test_gt026_positive_binop_promotes_to_wide():
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            a = jnp.zeros((8, 128), jnp.int32)
            big = 2 ** 40
            return a + big
    """, "GT026")
    assert [h[0] for h in hits] == ["GT026"]


def test_gt026_positive_dataflow_resolved_creation():
    # the wide dtype arrives through a VARIABLE — the syntactic GT009
    # token scan cannot see it, only the dataflow rule can
    hits = rules_hit("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            dt = jnp.float64
            return jnp.zeros((8, 128), dt)
    """, "GT026")
    assert [h[0] for h in hits] == ["GT026"]


def test_gt026_negative_narrow_and_host_scope():
    assert rules_hit("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            a = jnp.zeros((8, 128), jnp.float32)
            b = a.astype(jnp.bfloat16)
            return a + b
    """, "GT026") == []
    # host scope: wide numpy math is not the device contract's business
    assert rules_hit("""
        import numpy as np

        def f(x):
            return np.asarray(x, np.float64) * 2.0
    """, "GT026") == []


# ---------------------------------------------------------------------------
# GT027 contextvar read under pool
# ---------------------------------------------------------------------------

def test_gt027_positive_submit_reads_ctxvar():
    hits = rules_hit("""
        from greptimedb_tpu.telemetry import tracing

        def work():
            return tracing.current_span()

        def go(pool):
            pool.submit(work)
    """, "GT027")
    assert [(r, ln) for r, ln in hits] == [("GT027", 8)]


def test_gt027_positive_transitive_read():
    # the read is two call hops below the submitted function
    hits = rules_hit("""
        from greptimedb_tpu.util import deadline

        def leaf():
            deadline.check("leaf")

        def mid():
            leaf()

        def go(pool):
            pool.submit(mid)
    """, "GT027")
    assert [h[0] for h in hits] == ["GT027"]


def test_gt027_negative_parent_captured_and_plain_work():
    # the fix idiom: capture on the submitting thread, rebind inside
    assert rules_hit("""
        from greptimedb_tpu.telemetry import tracing

        def work(parent):
            with tracing.child_span("job", _parent=parent):
                return 1

        def go(pool):
            parent = tracing.current_span()
            pool.submit(work, parent)
    """, "GT027") == []
    # a submitted function that touches no ambient context is fine
    assert rules_hit("""
        def work(n):
            return n * 2

        def go(pool):
            pool.submit(work, 3)
    """, "GT027") == []


# ---------------------------------------------------------------------------
# shipped kernels stay silent
# ---------------------------------------------------------------------------

def test_shipped_kernels_clean_under_dataflow_rules():
    """The production kernels must produce no ACTIVE GT023-GT027
    findings."""
    import os

    from greptimedb_tpu.tools.lint.runner import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    kdir = os.path.join(repo, "greptimedb_tpu", "parallel", "kernels")
    res = lint_paths([kdir], baseline=None, select=DATAFLOW_RULES)
    assert res["findings"] == [], res["findings"]


# ---------------------------------------------------------------------------
# --explain: every rule's shipped examples are real
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rid", sorted(all_rules()))
def test_explain_examples_validate(rid):
    rule = all_rules()[rid]
    assert rule.example_pos, f"{rid} ships no firing example"
    assert rule.example_neg, f"{rid} ships no clean example"
    act, _ = lint_source("greptimedb_tpu/example.py", rule.example_pos,
                         select={rid})
    assert any(f.rule == rid for f in act), (
        f"{rid}'s 'Fires on' example does not fire"
    )
    act, _ = lint_source("greptimedb_tpu/example.py", rule.example_neg,
                         select={rid})
    assert act == [], (
        f"{rid}'s 'Stays silent on' example fires: "
        f"{[(f.rule, f.line) for f in act]}"
    )


def test_explain_cli_known_rule():
    buf = io.StringIO()
    assert explain_rule("gt027", out=buf) == 0
    text = buf.getvalue()
    assert "GT027" in text
    assert "Fires on:" in text
    assert "Stays silent on:" in text
    assert "disable=GT027" in text


def test_explain_cli_unknown_rule_exit_2(capsys):
    assert explain_rule("GT999") == 2
    assert "unknown rule id" in capsys.readouterr().err
