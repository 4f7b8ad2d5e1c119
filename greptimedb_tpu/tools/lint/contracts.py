"""gtcontract: whole-program wire/config/metric contract model.

GreptimeDB's disaggregated layers talk through hand-maintained string
contracts — Flight ticket fields and action names, `[gtdb:<code>]`
error markers, `[section] knob` TOML paths, `gtpu_*` metric families.
Every rule before this file checks one function or one file; the drift
that actually bites crosses the producer/consumer boundary (the repo's
history re-discovered the ticket strip-set invariant three separate
times, once per new side-channel field).

This module harvests a **ContractModel** from the parsed-AST forest of
the whole program — the runner parses each file exactly once and hands
the same trees to the per-file walk and to this pass — and checks five
cross-file rules over it:

  GT028  ticket field spliced into a partial_sql ticket but missing
         from the datanode decode-memo strip set (or stale/unapplied
         strip entries, or stripped fields never re-anchored)
  GT029  config knob read-but-undeclared, declared-but-never-read, or
         declared-but-undocumented (README)
  GT030  typed error whose StatusCode has no wire representative in
         _CODE_CLASSES, inconsistent representatives, duplicate enum
         code numbers, dead HTTP status-table entries
  GT031  metric family referenced-but-unregistered, or registered at
         multiple sites with drifting kind/label sets
  GT032  Flight action dispatched with no server handler, handled but
         never dispatched, or out of sync with list_actions()

Every check requires ALL of its surfaces to be present in the forest
(a producer AND the decode module, a handler module AND a dispatcher,
...), so partial scans — one file under `--changed`, or a fixture
mini-project in a test — only fire checks they can actually decide.
The explain examples are single-file mini-projects that carry both
sides of their contract for exactly this reason.
"""

from __future__ import annotations

import ast
import dataclasses
import re

from greptimedb_tpu.tools.lint.core import (
    Finding,
    Rule,
    dotted_name,
    register,
)

CONTRACT_RULE_IDS = ("GT028", "GT029", "GT030", "GT031", "GT032")

# a partial_sql ticket producer: the base JSON prefix every fan-out
# splice starts from (dist/dist_query.py builds tickets byte-wise so
# hot queries ship byte-identical tickets and hit the datanode's
# decode memo)
_PRODUCER_MARKERS = ('"rpc":"partial_sql"', '"rpc": "partial_sql"')
# a volatile side-channel splice: a bare `"field":<payload>,` JSON
# fragment concatenated into the ticket per call (deadline_s /
# traceparent / since_ms all take this shape); identity fields live in
# the base literal and are MEANT to key the memo
_FRAG_RE = re.compile(r'^"([a-z_][a-z0-9_]*)":.+,$', re.S)
# a strip-set entry: a compiled regex whose pattern removes one
# `"field":...` fragment from the raw ticket before the memo lookup
_STRIP_RE = re.compile(r'^"([a-z_][a-z0-9_]*)":')

_METRIC_NAME_RE = re.compile(r"^(?:gtpu|greptime)_[a-z0-9_]*[a-z0-9]$")
# bare string literals count as metric references only when they carry
# a conventional family suffix — bare `gtpu_span` / `greptime_value`
# style names are contextvars, column names, pool names
_METRIC_SUFFIXES = ("_total", "_seconds", "_ms", "_bytes",
                    "_bucket", "_sum", "_count")
# prometheus exposition derives these from a histogram family name
_HISTO_DERIVED = ("_bucket", "_sum", "_count")

_REG_KINDS = ("counter", "gauge", "histogram")


@dataclasses.dataclass(frozen=True)
class Site:
    path: str
    line: int
    col: int = 0

    def to_doc(self) -> dict:
        return {"path": self.path, "line": self.line}


def _const_str(node: ast.AST) -> str | None:
    """The text of a str/bytes constant (bytes decoded latin-1 — the
    ticket splices are bytes literals)."""
    if isinstance(node, ast.Constant):
        if isinstance(node.value, str):
            return node.value
        if isinstance(node.value, bytes):
            try:
                return node.value.decode("latin-1")
            except UnicodeDecodeError:
                return None
    return None


def _registry_receiver(func: ast.AST, attrs: tuple[str, ...]) -> bool:
    f = dotted_name(func)
    if f is None:
        return False
    parts = f.split(".")
    if parts[-1] not in attrs or len(parts) < 2:
        return False
    recv = parts[-2].lstrip("_").lower()
    return recv == "registry" or recv.endswith("registry")


def _opts_receiver(func: ast.AST) -> bool:
    f = dotted_name(func)
    if f is None or "." not in f:
        return False
    recv = f.split(".")[-2].lstrip("_").lower()
    return recv in ("opts", "options") or recv.endswith(("opts",
                                                         "options"))


class ContractModel:
    """Everything the cross-file rules need, with source locations."""

    def __init__(self):
        # -- partial_sql tickets ---------------------------------------
        self.ticket_producers: dict[str, list[Site]] = {}
        self.ticket_strips: dict[str, list[Site]] = {}
        self.ticket_strip_vars: dict[str, set[str]] = {}
        self.ticket_sub_applied: set[str] = set()   # strip var names
        self.ticket_reanchors: set[str] = set()     # decode-module keys
        self.has_producer_surface = False
        self.has_decode_surface = False
        # -- Flight actions --------------------------------------------
        self.action_dispatches: dict[str, list[Site]] = {}
        self.action_handlers: dict[str, list[Site]] = {}
        self.action_advertised: dict[str, list[Site]] = {}
        self.has_handler_surface = False
        self.has_advertise_surface = False
        # -- typed errors ----------------------------------------------
        self.status_codes: dict[str, tuple[int, Site]] = {}
        self.status_code_dups: list[tuple[str, str, int, Site]] = []
        self.error_classes: dict[str, tuple[str, Site]] = {}
        self.code_classes: dict[str, tuple[str, Site]] = {}
        self.http_status: dict[str, tuple[int, Site]] = {}
        self.has_error_surface = False
        self.has_code_map = False
        self.has_http_surface = False
        # -- config knobs ----------------------------------------------
        self.knob_defaults: dict[str, tuple[str, Site]] = {}
        self.knob_sections: dict[str, Site] = {}    # top-level dicts
        self.knob_dynamic: set[str] = set()         # `{}` leaves
        self.knob_reads: dict[str, list[Site]] = {}     # dotted gets
        self.section_reads: dict[str, list[Site]] = {}  # .section("s")
        self.opts_get_reads: dict[str, list[Site]] = {}
        # every identifier-shaped token in the program (names,
        # attributes, parameter names, string keys) EXCEPT the DEFAULTS
        # declaration keys themselves: section dicts are consumed
        # through dataclass fields, **kwargs, and key iteration the
        # extractor cannot resolve, so "never read" must mean the knob
        # name appears NOWHERE — anything weaker false-positives on
        # config objects built with from_options()-style constructors
        self.name_pool: set[str] = set()
        self.has_config_surface = False
        # -- metric families -------------------------------------------
        self.metric_regs: dict[
            str, list[tuple[str, tuple[str, ...] | None, Site]]] = {}
        self.metric_refs: dict[str, list[Site]] = {}
        # README text for the documentation check (None = not in scope,
        # e.g. fixture mini-projects — the check is skipped)
        self.readme_text: str | None = None

    # ------------------------------------------------------------------
    def to_doc(self) -> dict:
        def sites(m):
            return {k: [s.to_doc() for s in v]
                    for k, v in sorted(m.items())}

        return {
            "tickets": {
                "producers": sites(self.ticket_producers),
                "strips": sites(self.ticket_strips),
                "reanchors": sorted(self.ticket_reanchors),
            },
            "actions": {
                "dispatches": sites(self.action_dispatches),
                "handlers": sites(self.action_handlers),
                "advertised": sites(self.action_advertised),
            },
            "errors": {
                "codes": {k: {"value": v, **s.to_doc()}
                          for k, (v, s) in sorted(
                              self.status_codes.items())},
                "classes": {k: {"code": c, **s.to_doc()}
                            for k, (c, s) in sorted(
                                self.error_classes.items())},
                "code_classes": {k: {"class": c, **s.to_doc()}
                                 for k, (c, s) in sorted(
                                     self.code_classes.items())},
                "http_status": {k: {"status": v, **s.to_doc()}
                                for k, (v, s) in sorted(
                                    self.http_status.items())},
            },
            "knobs": {
                "declared": {k: {"default": d, **s.to_doc()}
                             for k, (d, s) in sorted(
                                 self.knob_defaults.items())},
                "reads": sites(self.knob_reads),
                "section_reads": sites(self.section_reads),
            },
            "metrics": {
                "registered": {
                    k: [{"kind": kind,
                         "labels": list(labels) if labels is not None
                         else None, **s.to_doc()}
                        for kind, labels, s in v]
                    for k, v in sorted(self.metric_regs.items())
                },
                "references": sites(self.metric_refs),
            },
        }


# ----------------------------------------------------------------------
# extraction
# ----------------------------------------------------------------------

# per-file partial models, keyed by (path -> hash(source)): extraction
# is a pure function of one file's text, so repeated extract_model
# calls in one process (the test suite runs dozens — every lint_paths
# call re-extracts the aux-harvested repo) only re-harvest files whose
# text actually changed. Cross-file state (StatusCode duplicate values)
# is reconstructed in _merge_model, never inside a partial.
_PARTIAL_CACHE: dict[str, tuple[int, "ContractModel"]] = {}


def extract_model(forest: dict[str, tuple[str, ast.Module]],
                  readme_text: str | None = None) -> ContractModel:
    """Harvest the contract model from {path: (source, tree)}."""
    model = ContractModel()
    model.readme_text = readme_text
    for path in sorted(forest):
        source, tree = forest[path]
        key = hash(source)
        hit = _PARTIAL_CACHE.get(path)
        if hit is not None and hit[0] == key:
            part = hit[1]
        else:
            part = ContractModel()
            _harvest_module(part, path, tree)
            _PARTIAL_CACHE[path] = (key, part)
        _merge_model(model, part)
    return model


def _merge_model(model: ContractModel, part: ContractModel) -> None:
    """Fold one file's partial model into the whole-program model.
    Cached partials are shared across calls: copy container contents,
    never alias them."""
    for attr in ("ticket_producers", "ticket_strips",
                 "action_dispatches", "action_handlers",
                 "action_advertised", "knob_reads", "section_reads",
                 "opts_get_reads", "metric_regs", "metric_refs"):
        dst = getattr(model, attr)
        for k, v in getattr(part, attr).items():
            dst.setdefault(k, []).extend(v)
    for k, v in part.ticket_strip_vars.items():
        model.ticket_strip_vars.setdefault(k, set()).update(v)
    for attr in ("ticket_sub_applied", "ticket_reanchors",
                 "knob_dynamic", "name_pool"):
        getattr(model, attr).update(getattr(part, attr))
    for attr in ("has_producer_surface", "has_decode_surface",
                 "has_handler_surface", "has_advertise_surface",
                 "has_error_surface", "has_code_map",
                 "has_http_surface", "has_config_surface"):
        if getattr(part, attr):
            setattr(model, attr, True)
    # within-file duplicates were found by the partial harvest;
    # cross-file duplicates are found here, against everything merged
    # from earlier (sorted-path) files — same order the single-pass
    # accumulation used
    model.status_code_dups.extend(part.status_code_dups)
    prior_items = list(model.status_codes.items())
    for name, (val, site) in part.status_codes.items():
        for prior, (pval, _) in prior_items:
            if pval == val:
                model.status_code_dups.append((name, prior, val, site))
        model.status_codes[name] = (val, site)
    for attr in ("error_classes", "code_classes", "http_status",
                 "knob_defaults", "knob_sections"):
        getattr(model, attr).update(getattr(part, attr))


def _harvest_module(model: ContractModel, path: str, tree: ast.Module):
    nodes = list(ast.walk(tree))
    _harvest_tickets(model, path, nodes)
    _harvest_actions(model, path, nodes)
    _harvest_errors(model, path, nodes)
    _harvest_knobs(model, path, tree, nodes)
    _harvest_metrics(model, path, nodes)


def _site(path: str, node: ast.AST) -> Site:
    return Site(path, getattr(node, "lineno", 1),
                getattr(node, "col_offset", 0))


# -- tickets -----------------------------------------------------------

def _harvest_tickets(model: ContractModel, path: str,
                     nodes: list[ast.AST]):
    # name -> fragment constants reachable through an assignment to it
    # (dist_query builds `dl_field = b'' if ... else b'"deadline_s":...,'`
    # then concatenates the names into the base literal)
    assigned_frags: dict[str, list[tuple[str, ast.AST]]] = {}
    assigned_base: set[str] = set()
    produced = False
    for node in nodes:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            for sub in ast.walk(node.value):
                s = _const_str(sub)
                if s is None:
                    continue
                if any(m in s for m in _PRODUCER_MARKERS):
                    assigned_base.add(name)
                m = _FRAG_RE.match(s)
                if m and not s.startswith("{"):
                    assigned_frags.setdefault(name, []).append(
                        (m.group(1), sub))

    def chain_parts(b: ast.AST) -> list[ast.AST]:
        if isinstance(b, ast.BinOp) and isinstance(b.op, ast.Add):
            return chain_parts(b.left) + chain_parts(b.right)
        return [b]

    for node in nodes:
        s = _const_str(node)
        if s is not None and any(m in s for m in _PRODUCER_MARKERS):
            produced = True
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Add)):
            continue
        parts = chain_parts(node)
        has_base = False
        frags: list[tuple[str, ast.AST]] = []
        for part in parts:
            for sub in ast.walk(part):
                ps = _const_str(sub)
                if ps is not None and any(
                        m in ps for m in _PRODUCER_MARKERS):
                    has_base = True
                m = _FRAG_RE.match(ps) if ps is not None else None
                if m and not ps.startswith("{"):
                    frags.append((m.group(1), sub))
                if isinstance(sub, ast.Name):
                    if sub.id in assigned_base:
                        has_base = True
                    frags.extend(assigned_frags.get(sub.id, ()))
        if has_base:
            model.has_producer_surface = True
            for field, fnode in frags:
                model.ticket_producers.setdefault(field, []).append(
                    _site(path, fnode))
    if produced:
        model.has_producer_surface = True

    # decode/strip surface: the module owning the ticket decode memo
    decode_here = False
    for node in nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == "_decode_ticket":
            decode_here = True
        if isinstance(node, ast.Call):
            f = dotted_name(node.func)
            if f is not None and f.split(".")[-1] == "_decode_ticket":
                decode_here = True
    if not decode_here:
        return
    model.has_decode_surface = True
    for node in nodes:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call):
            f = dotted_name(node.value.func)
            if f in ("re.compile", "compile") and node.value.args:
                pat = _const_str(node.value.args[0])
                m = _STRIP_RE.match(pat) if pat is not None else None
                if m:
                    field = m.group(1)
                    model.ticket_strips.setdefault(field, []).append(
                        _site(path, node))
                    model.ticket_strip_vars.setdefault(field, set()).add(
                        node.targets[0].id)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "sub":
            recv = dotted_name(node.func.value)
            if recv is not None:
                model.ticket_sub_applied.add(recv.split(".")[-1])
        # re-anchor reads: doc.get("field") / doc["field"] in the
        # decode module — the stripped value must be consumed from the
        # PARSED doc, not the memo-keyed raw bytes
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" and node.args:
            key = _const_str(node.args[0])
            if key is not None:
                model.ticket_reanchors.add(key)
        if isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load):
            key = _const_str(node.slice)
            if key is not None:
                model.ticket_reanchors.add(key)


# -- Flight actions ----------------------------------------------------

def _harvest_actions(model: ContractModel, path: str,
                     nodes: list[ast.AST]):
    # handler functions live only in modules that define the Flight
    # do_action entry point — `kind == "x"` matching in unrelated
    # `*_action` helpers (e.g. the manifest's apply_action) is a
    # different string namespace entirely
    module_has_do_action = any(
        isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and n.name in ("do_action", "_do_action")
        for n in nodes)
    for node in nodes:
        if isinstance(node, ast.Call) and node.args:
            name = _const_str(node.args[0])
            # `<anything>.action("x", ...)` — the receiver may itself
            # be a call (`self._flow_client_for(addr).action(...)`),
            # and `flight.Action("x", ...)` / `Action("x", ...)`
            attr = (node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else node.func.id
                    if isinstance(node.func, ast.Name) else None)
            if name is not None and attr in ("action", "Action"):
                model.action_dispatches.setdefault(name, []).append(
                    _site(path, node))
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in ("do_action", "_do_action"):
                model.has_handler_surface = True
            if module_has_do_action and (
                    node.name.endswith("_action")
                    or node.name == "do_action"):
                _harvest_handler_names(model, path, node)
            if node.name == "list_actions":
                model.has_advertise_surface = True
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Tuple) and len(sub.elts) == 2:
                        name = _const_str(sub.elts[0])
                        desc = _const_str(sub.elts[1])
                        if name is not None and desc is not None:
                            model.action_advertised.setdefault(
                                name, []).append(_site(path, sub))


def _harvest_handler_names(model: ContractModel, path: str,
                           fn: ast.AST):
    """Action names an action-handler function matches: `kind == "x"`
    comparisons and `kind in ("a", "b")` membership tests."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Compare) or len(node.ops) != 1:
            continue
        op = node.ops[0]
        lhs, rhs = node.left, node.comparators[0]
        if isinstance(op, (ast.Eq, ast.NotEq)):
            for a, b in ((lhs, rhs), (rhs, lhs)):
                name = _const_str(a)
                if name is not None and isinstance(b, ast.Name):
                    model.action_handlers.setdefault(name, []).append(
                        _site(path, node))
        elif isinstance(op, (ast.In, ast.NotIn)) \
                and isinstance(lhs, ast.Name) \
                and isinstance(rhs, (ast.Tuple, ast.List, ast.Set)):
            for el in rhs.elts:
                name = _const_str(el)
                if name is not None:
                    model.action_handlers.setdefault(name, []).append(
                        _site(path, el))


# -- typed errors ------------------------------------------------------

def _harvest_errors(model: ContractModel, path: str,
                    nodes: list[ast.AST]):
    for node in nodes:
        if isinstance(node, ast.ClassDef) and node.name == "StatusCode":
            model.has_error_surface = True
            for st in node.body:
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and isinstance(st.targets[0], ast.Name) \
                        and isinstance(st.value, ast.Constant) \
                        and isinstance(st.value.value, int):
                    name = st.targets[0].id
                    val = st.value.value
                    for prior, (pval, _) in model.status_codes.items():
                        if pval == val:
                            model.status_code_dups.append(
                                (name, prior, val, _site(path, st)))
                    model.status_codes[name] = (val, _site(path, st))
        elif isinstance(node, ast.ClassDef):
            for st in node.body:
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and getattr(st.targets[0], "id", None) \
                        == "status_code":
                    code = dotted_name(st.value)
                    if code is not None and "StatusCode" in code:
                        model.error_classes[node.name] = (
                            code.split(".")[-1], _site(path, node))
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and getattr(node.targets[0], "id", None) \
                == "_CODE_CLASSES" and isinstance(node.value, ast.Dict):
            model.has_code_map = True
            for k, v in zip(node.value.keys, node.value.values):
                code = dotted_name(k) if k is not None else None
                cls = dotted_name(v)
                if code is not None and "StatusCode" in code \
                        and cls is not None:
                    model.code_classes[code.split(".")[-1]] = (
                        cls.split(".")[-1], _site(path, k))
        # an HTTP status table: a dict literal mapping StatusCode
        # attributes to integer statuses (servers/http.py)
        if isinstance(node, ast.Dict) and len(node.keys) >= 3:
            entries = []
            for k, v in zip(node.keys, node.values):
                code = dotted_name(k) if k is not None else None
                if code is None or "StatusCode" not in code:
                    entries = None
                    break
                if not (isinstance(v, ast.Constant)
                        and isinstance(v.value, int)):
                    entries = None
                    break
                entries.append((code.split(".")[-1], v.value,
                                _site(path, k)))
            if entries:
                model.has_http_surface = True
                for code, status, site in entries:
                    model.http_status[code] = (status, site)


# -- config knobs ------------------------------------------------------

_IDENT_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def _harvest_knobs(model: ContractModel, path: str, tree: ast.Module,
                   nodes: list[ast.AST]):
    declared_keys: set[int] = set()
    for node in tree.body:
        value = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and getattr(node.targets[0], "id", None) == "DEFAULTS":
            value = node.value
        elif isinstance(node, ast.AnnAssign) \
                and getattr(node.target, "id", None) == "DEFAULTS":
            value = node.value
        if isinstance(value, ast.Dict):
            model.has_config_surface = True
            for sub in ast.walk(value):
                if isinstance(sub, ast.Dict):
                    declared_keys.update(id(k) for k in sub.keys
                                         if k is not None)
            _walk_defaults(model, path, value, [])
    for node in nodes:
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) and node.args:
            key = _const_str(node.args[0])
            if key is not None and node.func.attr == "get" \
                    and "." in key:
                model.knob_reads.setdefault(key, []).append(
                    _site(path, node))
                model.section_reads.setdefault(
                    key.split(".")[0], []).append(_site(path, node))
            elif key is not None and node.func.attr == "get" \
                    and _opts_receiver(node.func):
                model.opts_get_reads.setdefault(key, []).append(
                    _site(path, node))
            elif key is not None and node.func.attr == "section":
                model.section_reads.setdefault(key, []).append(
                    _site(path, node))
        if isinstance(node, ast.Name):
            model.name_pool.add(node.id)
        elif isinstance(node, ast.Attribute):
            model.name_pool.add(node.attr)
        elif isinstance(node, ast.arg):
            model.name_pool.add(node.arg)
        elif isinstance(node, ast.keyword) and node.arg is not None:
            model.name_pool.add(node.arg)
        elif isinstance(node, ast.Constant) \
                and id(node) not in declared_keys:
            s = _const_str(node)
            if s is not None and _IDENT_RE.match(s):
                model.name_pool.add(s)


def _walk_defaults(model: ContractModel, path: str, d: ast.Dict,
                   prefix: list[str]):
    for k, v in zip(d.keys, d.values):
        key = _const_str(k) if k is not None else None
        if key is None:
            continue
        dotted = ".".join(prefix + [key])
        if isinstance(v, ast.Dict) and v.keys:
            if not prefix:
                model.knob_sections[dotted] = _site(path, k)
            _walk_defaults(model, path, v, prefix + [key])
        elif isinstance(v, ast.Dict):
            # `{}` default: a dynamic table (e.g. scheduler.tenants) —
            # reads underneath it cannot be checked statically
            model.knob_dynamic.add(dotted)
            model.knob_defaults[dotted] = ("{}", _site(path, k))
        else:
            try:
                default = ast.unparse(v)
            except Exception:   # pragma: no cover - unparse is total
                default = "?"
            model.knob_defaults[dotted] = (default, _site(path, k))


# -- metric families ---------------------------------------------------

def _harvest_metrics(model: ContractModel, path: str,
                     nodes: list[ast.AST]):
    reg_name_nodes: set[int] = set()
    for node in nodes:
        if not isinstance(node, ast.Call):
            continue
        f = dotted_name(node.func)
        if f is not None and f.split(".")[-1] == "ContextVar" \
                and node.args:
            # ContextVar("gtpu_since_ms") names a contextvar, not a
            # metric family — even when it carries a unit suffix
            reg_name_nodes.add(id(node.args[0]))
        if _registry_receiver(node.func, _REG_KINDS) and node.args:
            name = _const_str(node.args[0])
            if name is None:
                continue
            reg_name_nodes.add(id(node.args[0]))
            kind = dotted_name(node.func).split(".")[-1]
            labels_node = None
            for kw in node.keywords:
                if kw.arg == "labels":
                    labels_node = kw.value
            if labels_node is None and len(node.args) >= 3:
                labels_node = node.args[2]
            labels: tuple[str, ...] | None = None
            if isinstance(labels_node, (ast.Tuple, ast.List)):
                lab = [_const_str(el) for el in labels_node.elts]
                if all(x is not None for x in lab):
                    labels = tuple(lab)
            model.metric_regs.setdefault(name, []).append(
                (kind, labels, _site(path, node)))
        elif _registry_receiver(node.func, ("get",)) and node.args:
            name = _const_str(node.args[0])
            if name is not None and _METRIC_NAME_RE.match(name):
                model.metric_refs.setdefault(name, []).append(
                    _site(path, node))
    for node in nodes:
        if id(node) in reg_name_nodes:
            continue
        s = _const_str(node)
        if s is None or not isinstance(node, ast.Constant) \
                or not isinstance(node.value, str):
            continue
        if _METRIC_NAME_RE.match(s) and s.endswith(_METRIC_SUFFIXES):
            model.metric_refs.setdefault(s, []).append(
                _site(path, node))


# ----------------------------------------------------------------------
# rules
# ----------------------------------------------------------------------

class ContractRule(Rule):
    """Cross-file rule: no visit_* methods; the runner calls check()
    with the whole-program model after the per-file walk."""

    def check(self, model: ContractModel) -> list[Finding]:
        raise NotImplementedError

    def _finding(self, site: Site, message: str) -> Finding:
        return Finding(rule=self.id, path=site.path, line=site.line,
                       col=site.col, message=message)


@register
class TicketFieldNotStripped(ContractRule):
    id = "GT028"
    name = "ticket-field-not-stripped"
    description = (
        "The frontend splices volatile per-call fields (deadline_s, "
        "traceparent, since_ms, ...) into the partial_sql ticket as "
        "`\"field\":...,` fragments; the datanode memoizes plan decode "
        "on the RAW ticket bytes, so every such field must be removed "
        "by the strip-set regexes in the decode module before the memo "
        "lookup — and re-anchored from the parsed doc. A spliced field "
        "with no strip entry silently defeats the decode memo for "
        "every query that carries it; a strip entry for a field no "
        "longer produced is dead; a strip regex never applied via "
        ".sub() strips nothing; a stripped field never read back from "
        "the doc is lost server-side. Fires only when both the "
        "producer and the decode module are in the linted set."
    )
    example_pos = '''\
import re

def encode(deadline, epoch):
    dl_field = b'' if deadline is None \\
        else b'"deadline_s":%.3f,' % deadline
    ep_field = b'"epoch_ms":%d,' % epoch
    return (b'{"rpc":"partial_sql",' + dl_field + ep_field
            + b'"mode":"plan","plan":null}')

_DEADLINE_FIELD_RE = re.compile(r'"deadline_s":[0-9.eE+-]+,')

def _decode_ticket(raw, doc):
    return raw

def exec_partial(raw, doc):
    raw = _DEADLINE_FIELD_RE.sub("", raw, count=1)
    plan = _decode_ticket(raw, doc)
    return plan, doc.get("deadline_s")
'''
    example_neg = '''\
import re

def encode(deadline, epoch):
    dl_field = b'' if deadline is None \\
        else b'"deadline_s":%.3f,' % deadline
    ep_field = b'"epoch_ms":%d,' % epoch
    return (b'{"rpc":"partial_sql",' + dl_field + ep_field
            + b'"mode":"plan","plan":null}')

_DEADLINE_FIELD_RE = re.compile(r'"deadline_s":[0-9.eE+-]+,')
_EPOCH_FIELD_RE = re.compile(r'"epoch_ms":-?\\d+,')

def _decode_ticket(raw, doc):
    return raw

def exec_partial(raw, doc):
    raw = _DEADLINE_FIELD_RE.sub("", raw, count=1)
    raw = _EPOCH_FIELD_RE.sub("", raw, count=1)
    plan = _decode_ticket(raw, doc)
    return plan, (doc.get("deadline_s"), doc.get("epoch_ms"))
'''

    def check(self, model: ContractModel) -> list[Finding]:
        out: list[Finding] = []
        if model.has_decode_surface:
            for field, sites in sorted(model.ticket_producers.items()):
                if field not in model.ticket_strips:
                    out.append(self._finding(
                        sites[0],
                        f"ticket field {field!r} is spliced into the "
                        "partial_sql ticket per call but has no strip-"
                        "set regex in the decode module — it becomes "
                        "part of the datanode's decode-memo key and "
                        "defeats the plan cache; add a "
                        f"`\"{field}\":...` strip regex and re-anchor "
                        "the value from the parsed doc"))
                elif field not in model.ticket_reanchors:
                    out.append(self._finding(
                        model.ticket_strips[field][0],
                        f"ticket field {field!r} is stripped from the "
                        "decode-memo key but never read back "
                        f"(doc.get({field!r})) in the decode module — "
                        "the side-channel value is lost server-side"))
        if model.has_producer_surface:
            for field, sites in sorted(model.ticket_strips.items()):
                if field not in model.ticket_producers:
                    out.append(self._finding(
                        sites[0],
                        f"strip-set regex for ticket field {field!r} "
                        "matches nothing any producer splices — stale "
                        "entry (or the producer-side splice was "
                        "removed without its strip)"))
        for field, varnames in sorted(model.ticket_strip_vars.items()):
            if not varnames & model.ticket_sub_applied:
                out.append(self._finding(
                    model.ticket_strips[field][0],
                    f"strip regex for ticket field {field!r} is "
                    "compiled but never applied via .sub() — the "
                    "field still reaches the decode-memo key"))
        return out


@register
class ConfigKnobContract(ContractRule):
    id = "GT029"
    name = "config-knob-contract"
    description = (
        "Every `[section] knob` must exist in three places at once: "
        "config.py DEFAULTS (so TOML can set it and code has a "
        "fallback), at least one read site (opts.get(\"sec.knob\") or "
        "a section-dict read — a declared-but-never-read knob is dead "
        "weight that operators tune with no effect), and the README "
        "knob documentation. Fires on dotted reads of undeclared "
        "knobs, on whole sections and individual knobs no code path "
        "consults, and — when README text is in scope — on knobs the "
        "docs never mention. Dynamic tables (`{}` defaults, e.g. "
        "per-tenant maps) are exempt below their prefix."
    )
    example_pos = '''\
DEFAULTS = {
    "http": {"addr": "127.0.0.1:4000"},
    "opentsdb": {"enable": True},
}

def serve(opts):
    return opts.get("http.addr")
'''
    example_neg = '''\
DEFAULTS = {
    "http": {"addr": "127.0.0.1:4000"},
    "opentsdb": {"enable": True},
}

def serve(opts):
    if opts.get("opentsdb.enable"):
        return opts.get("http.addr")
'''

    def check(self, model: ContractModel) -> list[Finding]:
        if not model.has_config_surface:
            return []
        out: list[Finding] = []
        sections = set(model.knob_sections)
        top_scalars = {k for k in model.knob_defaults if "." not in k}
        dotted_read_prefixes = {k.split(".")[0]
                                for k in model.knob_reads}
        # read-but-undeclared (anchored at the read site)
        for key, sites in sorted(model.knob_reads.items()):
            first = key.split(".")[0]
            if first not in sections:
                continue    # not a config path (.get on a plain dict)
            if key in model.knob_defaults:
                continue
            if any(d.startswith(key + ".") for d in model.knob_defaults):
                continue    # a section-level read
            if any(key == dyn or key.startswith(dyn + ".")
                   for dyn in model.knob_dynamic):
                continue
            out.append(self._finding(
                sites[0],
                f"config knob {key!r} is read but not declared in "
                "config DEFAULTS — TOML can never set it and there is "
                "no documented default; add it to the "
                f"[{first}] section"))
        # declared-but-never-consulted sections
        for sec, site in sorted(model.knob_sections.items()):
            if sec in model.section_reads \
                    or sec in dotted_read_prefixes:
                continue
            out.append(self._finding(
                site,
                f"config section [{sec}] is declared in DEFAULTS but "
                "no code path consults it (no opts.section() or "
                "dotted get) — plumb it or delete it"))
        # declared-but-never-read knobs inside consulted sections
        for key, (_, site) in sorted(model.knob_defaults.items()):
            if "." not in key:
                if key not in model.name_pool:
                    out.append(self._finding(
                        site,
                        f"top-level config knob {key!r} is declared "
                        "but never read — plumb it or delete it"))
                continue
            sec = key.split(".")[0]
            if sec not in model.section_reads \
                    and sec not in dotted_read_prefixes:
                continue    # whole section already reported above
            if key in model.knob_reads:
                continue
            if key.split(".")[-1] in model.name_pool:
                continue    # consumed through a section dict / config
                #             object field somewhere
            if any(key == dyn or key.startswith(dyn + ".")
                   for dyn in model.knob_dynamic):
                continue
            out.append(self._finding(
                site,
                f"config knob {key!r} is declared in DEFAULTS but "
                "never read anywhere — operators can tune it with no "
                "effect; plumb it or delete it"))
        # declared-but-undocumented (only when README text is in scope)
        if model.readme_text is not None:
            for key, (_, site) in sorted(model.knob_defaults.items()):
                leaf = key.split(".")[-1]
                if leaf not in model.readme_text:
                    out.append(self._finding(
                        site,
                        f"config knob {key!r} is not documented in the "
                        "README knob tables — add a row (name, "
                        "default, one-line meaning)"))
        return out


@register
class ErrorCodeContract(ContractRule):
    id = "GT030"
    name = "error-code-contract"
    description = (
        "Typed errors cross the wire as `[gtdb:<code>]` markers and "
        "come back through error_from_code(), which needs a "
        "representative class per StatusCode in _CODE_CLASSES — a "
        "typed error whose code has no representative decodes to the "
        "generic base class on the client, losing the typed retry/"
        "degrade semantics. Also fires on _CODE_CLASSES entries whose "
        "representative class carries a different code, on duplicate "
        "integer code values (IntEnum silently aliases the second "
        "name), and on HTTP status-table entries for codes no typed "
        "error carries (dead mapping rows)."
    )
    example_pos = '''\
class StatusCode:
    RATE_LIMITED = 6001
    QUERY_TIMEOUT = 3002

class RateLimitedError(Exception):
    status_code = StatusCode.RATE_LIMITED

class QueryTimeoutError(Exception):
    status_code = StatusCode.QUERY_TIMEOUT

_CODE_CLASSES = {StatusCode.RATE_LIMITED: RateLimitedError}
'''
    example_neg = '''\
class StatusCode:
    RATE_LIMITED = 6001
    QUERY_TIMEOUT = 3002

class RateLimitedError(Exception):
    status_code = StatusCode.RATE_LIMITED

class QueryTimeoutError(Exception):
    status_code = StatusCode.QUERY_TIMEOUT

_CODE_CLASSES = {
    StatusCode.RATE_LIMITED: RateLimitedError,
    StatusCode.QUERY_TIMEOUT: QueryTimeoutError,
}
'''

    def check(self, model: ContractModel) -> list[Finding]:
        out: list[Finding] = []
        for name, prior, val, site in model.status_code_dups:
            out.append(self._finding(
                site,
                f"StatusCode.{name} duplicates code number {val} "
                f"already used by StatusCode.{prior} — IntEnum "
                "silently aliases the second name and the wire marker "
                "becomes ambiguous"))
        used_codes = {code for code, _ in model.error_classes.values()}
        if model.has_code_map:
            for cls, (code, site) in sorted(
                    model.error_classes.items()):
                if code not in model.code_classes:
                    out.append(self._finding(
                        site,
                        f"typed error {cls} carries StatusCode.{code} "
                        "but _CODE_CLASSES has no representative for "
                        "that code — error_from_code() will decode "
                        "the wire marker to the generic base class"))
            for code, (cls, site) in sorted(model.code_classes.items()):
                actual = model.error_classes.get(cls)
                if actual is not None and actual[0] != code:
                    out.append(self._finding(
                        site,
                        f"_CODE_CLASSES maps StatusCode.{code} to "
                        f"{cls}, whose own status_code is "
                        f"StatusCode.{actual[0]} — the wire round-"
                        "trip re-tags the error with a different "
                        "code"))
        if model.has_error_surface and model.has_http_surface \
                and model.error_classes:
            for code, (status, site) in sorted(
                    model.http_status.items()):
                if code not in model.status_codes:
                    out.append(self._finding(
                        site,
                        f"HTTP status table maps StatusCode.{code} "
                        "which is not a defined StatusCode member"))
                elif code not in used_codes:
                    out.append(self._finding(
                        site,
                        f"HTTP status table maps StatusCode.{code} "
                        f"to {status}, but no typed error carries "
                        "that code — dead mapping row"))
        return out


@register
class MetricFamilyContract(ContractRule):
    id = "GT031"
    name = "metric-family-contract"
    description = (
        "A `gtpu_*`/`greptime_*` metric family name referenced by a "
        "renderer or test (registry.get(), or a string "
        "literal carrying a conventional family suffix: _total, "
        "_seconds, _ms, _bytes, _bucket, _sum, _count) must be "
        "registered somewhere in the program — an unregistered "
        "reference raises KeyError on the scrape path or silently "
        "asserts against a family that can never exist. Registering "
        "the same family at multiple sites with different kinds or "
        "label sets fires too: exposition merges them into one "
        "family, and the self-export reingest keys on exact label "
        "names. `_bucket`/`_sum`/`_count` references resolve to their "
        "base histogram."
    )
    example_pos = '''\
from greptimedb_tpu.telemetry.metrics import global_registry

global_registry.counter("gtpu_rows_total", "rows written", ("table",))

def render(registry):
    return registry.get("gtpu_bytes_total").value()
'''
    example_neg = '''\
from greptimedb_tpu.telemetry.metrics import global_registry

global_registry.counter("gtpu_rows_total", "rows written", ("table",))

def render(registry):
    return registry.get("gtpu_rows_total").value()
'''

    def check(self, model: ContractModel) -> list[Finding]:
        out: list[Finding] = []
        for name, regs in sorted(model.metric_regs.items()):
            kinds = {k for k, _, _ in regs}
            if len(kinds) > 1:
                out.append(self._finding(
                    regs[1][2],
                    f"metric family {name!r} is registered with "
                    f"inconsistent kinds {sorted(kinds)} across sites "
                    "— exposition merges them into one family"))
            label_sets = {labels for _, labels, _ in regs
                          if labels is not None}
            if len(label_sets) > 1:
                out.append(self._finding(
                    regs[1][2],
                    f"metric family {name!r} is registered with "
                    "inconsistent label sets "
                    f"{sorted(map(list, label_sets))} — dashboards "
                    "and the self-export reingest key on exact label "
                    "names"))
        if not model.metric_regs:
            return out  # no registration surface in the linted set
        for name, sites in sorted(model.metric_refs.items()):
            if name in model.metric_regs:
                continue
            base = None
            for suf in _HISTO_DERIVED:
                if name.endswith(suf):
                    base = name[: -len(suf)]
                    break
            if base is not None and any(
                    kind == "histogram"
                    for kind, _, _ in model.metric_regs.get(base, ())):
                continue
            out.append(self._finding(
                sites[0],
                f"metric family {name!r} is referenced but never "
                "registered with any registry — registry.get() "
                "raises KeyError on this name (or the assertion can "
                "never match a live family)"))
        return out


@register
class FlightActionContract(ContractRule):
    id = "GT032"
    name = "flight-action-contract"
    description = (
        "Flight actions are a string-keyed RPC surface: every "
        "client-side dispatch (client.action(\"x\", ...) or a raw "
        "flight.Action(\"x\", ...)) needs a matching `kind == \"x\"` "
        "branch in the server's do_action handler, every handler "
        "branch needs at least one dispatcher (dead wire surface "
        "otherwise), and list_actions() must advertise exactly the "
        "handled set — clients discover capabilities from it. Fires "
        "only when the counterpart surface is in the linted set."
    )
    example_pos = '''\
def flush(client):
    return client.action("flush_region", b"{}")

def reset(client):
    return client.action("reset_region", b"{}")

class Server:
    def do_action(self, kind, body):
        if kind == "flush_region":
            return b"ok"
        raise KeyError(kind)

    def list_actions(self, context):
        return [("flush_region", "flush one region")]
'''
    example_neg = '''\
def flush(client):
    return client.action("flush_region", b"{}")

def reset(client):
    return client.action("reset_region", b"{}")

class Server:
    def do_action(self, kind, body):
        if kind == "flush_region":
            return b"ok"
        if kind == "reset_region":
            return b"ok"
        raise KeyError(kind)

    def list_actions(self, context):
        return [("flush_region", "flush one region"),
                ("reset_region", "reset one region")]
'''

    def check(self, model: ContractModel) -> list[Finding]:
        out: list[Finding] = []
        if model.has_handler_surface:
            for name, sites in sorted(model.action_dispatches.items()):
                if name not in model.action_handlers:
                    out.append(self._finding(
                        sites[0],
                        f"Flight action {name!r} is dispatched but no "
                        "do_action handler matches it — the server "
                        "returns unknown-action for every call"))
        if model.action_dispatches:
            for name, sites in sorted(model.action_handlers.items()):
                if name not in model.action_dispatches:
                    out.append(self._finding(
                        sites[0],
                        f"Flight action {name!r} has a server handler "
                        "but no dispatcher anywhere — dead wire "
                        "surface (add a client wrapper or remove the "
                        "branch)"))
        if model.has_advertise_surface and model.has_handler_surface:
            for name, sites in sorted(model.action_handlers.items()):
                if name not in model.action_advertised:
                    out.append(self._finding(
                        sites[0],
                        f"Flight action {name!r} is handled but not "
                        "advertised by list_actions() — clients "
                        "discovering capabilities never see it"))
            for name, sites in sorted(model.action_advertised.items()):
                if name not in model.action_handlers:
                    out.append(self._finding(
                        sites[0],
                        f"list_actions() advertises {name!r} but no "
                        "do_action branch handles it"))
        return out


def contract_findings(model: ContractModel,
                      rules: dict[str, Rule]) -> list[Finding]:
    """Run every selected contract rule over the model."""
    out: list[Finding] = []
    for rid in CONTRACT_RULE_IDS:
        rule = rules.get(rid)
        if isinstance(rule, ContractRule):
            out.extend(rule.check(model))
    return out
