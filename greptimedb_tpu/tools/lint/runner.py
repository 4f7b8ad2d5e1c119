"""gtlint runner + CLI.

    python -m greptimedb_tpu.tools.lint [paths...] [--format=json]
    greptimedb-tpu lint [paths...]

Exit status: 0 clean, 1 unsuppressed/non-baselined findings (or stale
baseline entries), 2 usage/parse errors.
"""

from __future__ import annotations

import argparse
import ast
import os
import sys

from greptimedb_tpu.tools.lint.baseline import Baseline
from greptimedb_tpu.tools.lint.core import (
    FileContext,
    Finding,
    ModuleLinter,
    all_rules,
)
from greptimedb_tpu.tools.lint.report import render_json, render_text
from greptimedb_tpu.tools.lint.suppress import Suppressions

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                "baseline.json")

# repo root (parent of the greptimedb_tpu package): finding paths are
# anchored here, NOT to os.getcwd(), so the checked-in baseline and
# the lint gate behave identically from any working directory
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _norm_path(path: str) -> str:
    ap = os.path.abspath(path)
    try:
        rel = os.path.relpath(ap, _REPO_ROOT)
    except ValueError:      # Windows: different drive
        rel = None
    if rel is not None and not rel.startswith(".."):
        return rel.replace("\\", "/")
    return ap.replace("\\", "/")


def _select_rules(select: set[str] | None):
    rules = all_rules()
    if select:
        rules = {k: v for k, v in rules.items() if k in select}
    return rules


def _walk_findings(path: str, source: str, tree: ast.Module,
                   rules) -> list[Finding]:
    """The per-file AST walk over an already-parsed tree."""
    ctx = FileContext(path, source, tree)
    ModuleLinter(ctx, rules).run()
    return ctx.findings


def lint_source(path: str, source: str, *, select: set[str] | None = None
                ) -> tuple[list[Finding], list[Finding]]:
    """Lint one file's text. Returns (active, suppressed) findings.

    Runs the per-file walk AND the contracts pass over a one-file
    forest: fixture mini-projects and the `--explain` examples carry
    both sides of their contract in a single module, so the cross-file
    rules are testable here too (checks whose counterpart surface is
    absent stay silent by construction)."""
    from greptimedb_tpu.tools.lint.contracts import (
        contract_findings,
        extract_model,
    )

    rules = _select_rules(select)
    tree = ast.parse(source, filename=path)
    findings = _walk_findings(path, source, tree, rules)
    findings = findings + contract_findings(
        extract_model({path: (source, tree)}), rules)
    sup = Suppressions(source)
    active = [f for f in findings if not sup.covers(f.rule, f.line)]
    suppressed = [f for f in findings if sup.covers(f.rule, f.line)]
    return active, suppressed


def iter_py_files(paths: list[str]):
    for p in paths:
        if os.path.isfile(p):
            yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)


def changed_files(ref: str) -> set[str] | None:
    """Absolute paths of .py files differing from `ref` (tracked
    changes plus untracked files); None when git cannot answer."""
    import subprocess

    out: list[str] = []
    for cmd in (
        ["git", "diff", "--name-only", "-z", ref, "--", "*.py"],
        ["git", "ls-files", "--others", "--exclude-standard", "-z",
         "--", "*.py"],
    ):
        try:
            r = subprocess.run(cmd, cwd=_REPO_ROOT,
                               capture_output=True, text=True,
                               timeout=30)
        except (OSError, subprocess.SubprocessError):
            return None
        if r.returncode != 0:
            return None
        out.extend(n for n in r.stdout.split("\0") if n)
    return {os.path.normpath(os.path.join(_REPO_ROOT, n))
            for n in out}


def _aux_paths(done: set[str]) -> list[str]:
    """Harvest-only files for the whole-program contracts pass: the
    rest of the package plus the repo's reference surfaces (tests
    hold metric-name references and action dispatches the contract
    model must see). Returns paths not already in `done`."""
    out: list[str] = []
    roots = [os.path.join(_REPO_ROOT, "greptimedb_tpu"),
             os.path.join(_REPO_ROOT, "tests")]
    for root in roots:
        if os.path.isdir(root):
            out.extend(iter_py_files([root]))
    return [p for p in out if _norm_path(p) not in done]


# text markers covering every construct the contract harvesters match:
# a scanned set containing NONE of these contributes nothing to the
# contract model, so the whole-repo aux harvest (which exists to supply
# the missing half of a contract whose other half IS in the scan) can
# be skipped and the pass run scan-only. Keeps `gtlint <tmp-fixture>`
# runs from re-parsing the repo to check fixtures that cannot
# participate in any contract.
_CONTRACT_MARKERS = (
    '"rpc":', "'rpc':", "_decode_ticket",            # tickets
    ".action(", "Action(", "do_action", "list_actions",  # actions
    "StatusCode", "_CODE_CLASSES",                   # errors
    "DEFAULTS", ".get(", ".section(",                # knobs
    "gtpu_", "greptime_", "registry",                # metrics
)


def _scan_has_contract_markers(
        forest: dict[str, tuple[str, ast.Module]]) -> bool:
    return any(any(m in text for m in _CONTRACT_MARKERS)
               for text, _ in forest.values())


# harvest-only files are parsed for the contract model, never walked
# by per-file rules, so their (text, tree, suppressions) triples are
# safe to reuse across lint_paths calls in one process — the test
# suite runs dozens, each of which would otherwise re-read and
# re-parse the whole repo. Keyed by (mtime_ns, size); an edit
# invalidates.
_AUX_CACHE: dict[str, tuple[int, int, str, ast.Module,
                            Suppressions]] = {}


def _load_aux(path: str, norm: str
              ) -> tuple[str, ast.Module, Suppressions] | None:
    try:
        st = os.stat(path)
        hit = _AUX_CACHE.get(norm)
        if hit is not None and hit[0] == st.st_mtime_ns \
                and hit[1] == st.st_size:
            return hit[2], hit[3], hit[4]
        with open(path, encoding="utf-8") as f:
            text = f.read()
        tree = ast.parse(text, filename=norm)
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None
    sup = Suppressions(text)
    _AUX_CACHE[norm] = (st.st_mtime_ns, st.st_size, text, tree, sup)
    return text, tree, sup


def _readme_text() -> str | None:
    readme = os.path.join(_REPO_ROOT, "README.md")
    try:
        with open(readme, encoding="utf-8") as f:
            return f.read()
    except OSError:
        return None


def lint_paths(paths: list[str], *, baseline: Baseline | None = None,
               select: set[str] | None = None,
               only: set[str] | None = None) -> dict:
    """Lint every .py under `paths`; returns the report document.
    `only` (absolute paths) restricts the walk — the --changed mode.

    Each file is parsed exactly ONCE: the tree feeds both the per-file
    walk and the whole-program contracts pass (GT028-GT032). The
    contracts pass is whole-program by construction — besides the
    scanned files it harvests the rest of the package, tests/ and
    README.md, so a subdirectory run still checks against the full
    contract surfaces. `--changed` runs skip it (a partial forest
    cannot decide cross-file contracts; the full gate run catches the
    drift)."""
    from greptimedb_tpu.tools.lint.contracts import (
        CONTRACT_RULE_IDS,
        contract_findings,
        extract_model,
    )

    rules = _select_rules(select)
    findings: list[Finding] = []
    suppressed: list[Finding] = []
    errors: list[tuple[str, str]] = []
    sources: dict[str, list[str]] = {}
    forest: dict[str, tuple[str, ast.Module]] = {}
    sup_cache: dict[str, Suppressions] = {}
    nfiles = 0
    for p in paths:
        if not os.path.exists(p):
            # a typo'd/renamed path must not lint 0 files and pass
            errors.append((p, "path does not exist"))
    for path in iter_py_files(paths):
        if only is not None and os.path.normpath(
                os.path.abspath(path)) not in only:
            continue
        nfiles += 1
        norm = _norm_path(path)
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
            tree = ast.parse(text, filename=norm)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            errors.append((norm, str(e)))
            continue
        sources[norm] = text.splitlines()
        forest[norm] = (text, tree)
        sup = sup_cache[norm] = Suppressions(text)
        for f in _walk_findings(norm, text, tree, rules):
            (suppressed if sup.covers(f.rule, f.line)
             else findings).append(f)

    if only is None and any(r in rules for r in CONTRACT_RULE_IDS):
        harvest = dict(forest)
        aux = (_aux_paths(set(forest))
               if _scan_has_contract_markers(forest) else [])
        for path in aux:
            norm = _norm_path(path)
            loaded = _load_aux(path, norm)
            if loaded is None:
                continue    # per-file lint of it reports the error
            text, tree, sup = loaded
            harvest[norm] = (text, tree)
            sources[norm] = text.splitlines()
            sup_cache[norm] = sup
        model = extract_model(harvest, readme_text=_readme_text())
        for f in contract_findings(model, rules):
            sup = sup_cache.get(f.path)
            if sup is not None and sup.covers(f.rule, f.line):
                suppressed.append(f)
            else:
                findings.append(f)

    def line_text(path: str, lineno: int) -> str:
        lines = sources.get(path, [])
        return lines[lineno - 1].strip() if 1 <= lineno <= len(lines) \
            else ""

    if baseline is not None:
        new, old, stale = baseline.split(findings, line_text)
        if only is not None:
            # a --changed run must not call entries for files it never
            # scanned "stale"; full runs keep full stale detection so
            # entries for DELETED files still get reported
            stale = [e for e in stale if e.get("path") in sources]
    else:
        new, old, stale = findings, [], []
    new.sort(key=lambda f: (f.path, f.line, f.rule))
    return {
        "findings": [f.to_doc() for f in new],
        "baselined": [f.to_doc() for f in old],
        "suppressed": [f.to_doc() for f in suppressed],
        "stale_baseline": stale,
        "errors": errors,
        "counts": {
            "files": nfiles, "new": len(new), "baselined": len(old),
            "suppressed": len(suppressed), "stale_baseline": len(stale),
        },
        "clean": not new and not stale and not errors,
        # internal (stripped before reporting): for --write-baseline
        "_line_text": line_text,
        "_scanned_paths": list(sources),
    }


def contracts_dump(paths: list[str], *, out=None) -> int:
    """`lint --contracts-dump`: emit the extracted whole-program
    contract model (tickets, actions, error codes, knobs, metric
    families, each with source locations) as JSON with stable key
    order. Debugging aid and docs-generation input; always exits 0."""
    import json

    from greptimedb_tpu.tools.lint.contracts import extract_model

    out = out or sys.stdout
    forest: dict[str, tuple[str, ast.Module]] = {}
    scan = list(iter_py_files(paths))
    scan += _aux_paths({_norm_path(p) for p in scan})
    for path in scan:
        norm = _norm_path(path)
        loaded = _load_aux(path, norm)
        if loaded is None:
            continue
        forest[norm] = (loaded[0], loaded[1])
    model = extract_model(forest, readme_text=_readme_text())
    print(json.dumps(model.to_doc(), indent=2, sort_keys=True),
          file=out)
    return 0


def explain_rule(rule_id: str, *, out=None) -> int:
    """`lint --explain GTxxx`: the rule's doc, its firing/clean
    examples (the same snippets the explain meta-test validates), and
    how to suppress it. Exit 2 on an unknown id."""
    import textwrap

    out = out or sys.stdout
    rid = rule_id.strip().upper()
    rule = all_rules().get(rid)
    if rule is None:
        known = ", ".join(all_rules())
        print(f"gtlint: unknown rule id {rule_id!r} (known: {known})",
              file=sys.stderr)
        return 2
    print(f"{rid} — {rule.name}", file=out)
    print("", file=out)
    print(textwrap.fill(rule.description, width=72), file=out)
    if rule.example_pos:
        print("\nFires on:\n", file=out)
        print(textwrap.indent(rule.example_pos.rstrip(), "    "),
              file=out)
    if rule.example_neg:
        print("\nStays silent on:\n", file=out)
        print(textwrap.indent(rule.example_neg.rstrip(), "    "),
              file=out)
    print(f"""
Suppression:

    <line>  # gtlint: disable={rid}        (this line)
    # gtlint: disable-next-line={rid}      (the next line)
    # gtlint: disable-file={rid}           (whole file; first 10 lines)

A suppression must carry an inline comment stating the contract that
makes the flagged code correct.""", file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="gtlint",
        description="AST-based correctness linter for greptimedb-tpu "
                    "(JAX/TPU + concurrency hazards).",
    )
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint "
                         "(default: the greptimedb_tpu package)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON path (default: the checked-in "
                         "package baseline)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report every finding")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings to the baseline file "
                         "and exit 0")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule ids to run (e.g. "
                         "GT001,GT007)")
    ap.add_argument("--changed", default=None, metavar="REF",
                    help="lint only files differing from this git ref "
                         "(tracked diff + untracked) — fast pre-commit "
                         "runs, e.g. --changed HEAD or --changed "
                         "origin/main")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--contracts-dump", action="store_true",
                    help="emit the extracted whole-program contract "
                         "model (tickets, actions, error codes, knobs, "
                         "metric families with source locations) as "
                         "JSON and exit 0")
    ap.add_argument("--explain", default=None, metavar="GTxxx",
                    help="print one rule's doc, a minimal firing and "
                         "clean example, and the suppression syntax; "
                         "exit 2 on an unknown id")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, rule in all_rules().items():
            print(f"{rid} {rule.name}: {rule.description}")
        return 0

    if args.explain:
        return explain_rule(args.explain)

    paths = args.paths or [os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))]

    if args.contracts_dump:
        return contracts_dump(paths)
    select = ({s.strip().upper() for s in args.select.split(",")
               if s.strip()} if args.select else None)
    baseline = None
    if not args.no_baseline and not args.write_baseline:
        baseline = Baseline.load(args.baseline)

    only = None
    if args.changed:
        only = changed_files(args.changed)
        if only is None:
            print(f"gtlint: git could not diff against "
                  f"{args.changed!r} (not a repo, or unknown ref?)",
                  file=sys.stderr)
            return 2
        if args.write_baseline:
            print("gtlint: --write-baseline cannot be combined with "
                  "--changed (a partial run would clobber the rest)",
                  file=sys.stderr)
            return 2

    result = lint_paths(paths, baseline=baseline, select=select,
                        only=only)
    line_text = result.pop("_line_text")
    scanned = set(result.pop("_scanned_paths", []))

    if args.write_baseline:
        if select:
            # a rule-filtered run would clobber other rules' entries
            # for the scanned files
            print("gtlint: --write-baseline cannot be combined with "
                  "--select", file=sys.stderr)
            return 2
        if result["errors"]:
            for p, msg in result["errors"]:
                print(f"{p}: error: {msg}", file=sys.stderr)
            print("gtlint: refusing to write a baseline from an "
                  "errored run", file=sys.stderr)
            return 2
        findings = [Finding(**d) for d in result["findings"]]
        new_base = Baseline.from_findings(findings, line_text)
        # merge: keep existing entries for files OUTSIDE this run's
        # scope so a subdirectory run doesn't discard the rest of the
        # grandfathered debt
        kept = [e for e in Baseline.load(args.baseline).entries
                if e.get("path") not in scanned]
        new_base.entries = kept + new_base.entries
        new_base.save(args.baseline)
        print(f"gtlint: wrote {len(new_base.entries)} entries to "
              f"{args.baseline}"
              + (f" ({len(kept)} kept from outside this run's scope)"
                 if kept else ""))
        return 0

    out = (render_json(result) if args.format == "json"
           else render_text(result))
    print(out)
    if result["errors"]:
        return 2
    return 0 if result["clean"] else 1


def run(paths: list[str], *, baseline_path: str | None = None,
        no_baseline: bool = False) -> dict:
    """Library entry: lint `paths`, returning the report document
    (used by tests/test_lint_clean.py and cli.py)."""
    baseline = None
    if not no_baseline:
        baseline = Baseline.load(baseline_path or DEFAULT_BASELINE)
    result = lint_paths(paths, baseline=baseline)
    result.pop("_line_text", None)
    result.pop("_scanned_paths", None)
    return result
