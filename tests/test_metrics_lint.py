"""Metrics-name lint (tier-1): walk the package source for every
``nanodiloco_*`` metric family and hold the exposition namespace to its
contract — rendered sample names globally unique (no family may collide
with another family's ``_total``/``_bucket``/``_count``/``_sum``
rendering), every label key drawn from a BOUNDED allowlist (a
``request_id``-like label would mint one series per request and melt
any scrape store), every consumer-side metric-name reference resolving
to a family some producer actually renders, and every family documented
in README's metrics tables. Each assertion fails naming the offender
and its definition site.

The scan is static (ast + regex over ``nanodiloco_tpu/``), matching the
three definition idioms in the tree: typed family tuples
``(name, "counter"|"gauge"|"histogram", help, samples)``, untyped
gauge-list entries ``(name, "help text", value...)`` (the help is prose
— it contains a space, which is what separates a definition from a
section-needle tuple), and gauge-dict assignments
``gauges["nanodiloco_x"] = v``."""

import ast
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "nanodiloco_tpu")

METRIC_TYPES = {"counter", "gauge", "histogram"}

# every label key any family may use. Additions need a README table row
# AND an entry here — the point is that adding an unbounded-cardinality
# label (request_id, prompt hash, ...) is a loud, reviewed decision,
# never an accident.
LABEL_ALLOWLIST = {
    "outcome", "reason", "result", "priority", "shard", "worker",
    "target", "kind", "op", "cause", "phase", "event", "state",
    "replica", "rule", "program", "tier", "direction", "role",
    "le",  # histogram bucket bound (rendered by the exposition layer)
}

# names that are legitimately NOT metric families
NON_METRIC_NAMES = {"nanodiloco_tpu"}  # the package itself


def _scan():
    """(defs, refs): definition sites {name: [(file, line, type)]} with
    label keys {name: set}, and every other nanodiloco_* string literal
    as a reference [(name, file)]."""
    defs: dict[str, list] = {}
    labels: dict[str, set] = {}
    refs: list[tuple[str, str]] = []
    for dirpath, _dirs, files in os.walk(PKG):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO)
            with open(path) as f:
                src = f.read()
            tree = ast.parse(src)
            claimed: set[str] = set()

            def add_def(name, lineno, mtype):
                defs.setdefault(name, []).append((rel, lineno, mtype))
                claimed.add(name)
                labels.setdefault(name, set())

            for node in ast.walk(tree):
                if isinstance(node, ast.Tuple) and len(node.elts) >= 2:
                    e0, e1 = node.elts[0], node.elts[1]
                    if not (isinstance(e0, ast.Constant)
                            and isinstance(e0.value, str)
                            and e0.value.startswith("nanodiloco_")):
                        continue
                    name = e0.value
                    if (isinstance(e1, ast.Constant)
                            and e1.value in METRIC_TYPES):
                        add_def(name, node.lineno, e1.value)
                        for sub in ast.walk(node):
                            if isinstance(sub, ast.Dict):
                                for k in sub.keys:
                                    if (isinstance(k, ast.Constant)
                                            and isinstance(k.value, str)):
                                        labels[name].add(k.value)
                    elif (isinstance(e1, ast.Constant)
                          and isinstance(e1.value, str)
                          and " " in e1.value):
                        # (name, "help text", ...) — untyped gauge-list /
                        # _GAUGE_KEYS entry; a 4-tuple's third string
                        # element is the loop's label key
                        add_def(name, node.lineno, "untyped")
                        if len(node.elts) >= 4:
                            e2 = node.elts[2]
                            if (isinstance(e2, ast.Constant)
                                    and isinstance(e2.value, str)):
                                labels[name].add(e2.value)
                elif isinstance(node, ast.Assign):
                    for tgt in node.targets:
                        if (isinstance(tgt, ast.Subscript)
                                and isinstance(tgt.slice, ast.Constant)
                                and isinstance(tgt.slice.value, str)
                                and tgt.slice.value.startswith(
                                    "nanodiloco_")):
                            add_def(tgt.slice.value, node.lineno, "untyped")
            for m in re.finditer(r'"(nanodiloco_[a-z0-9_]+)"', src):
                if m.group(1) not in claimed:
                    refs.append((m.group(1), rel))
    return defs, labels, refs


@pytest.fixture(scope="module")
def scan():
    return _scan()


def test_scan_finds_the_namespace(scan):
    """Sanity pin: the scan sees the known core families — if a
    refactor moves definitions to an idiom the scan can't parse, this
    fails before the other checks silently pass on nothing."""
    defs, _labels, _refs = scan
    for expected in ("nanodiloco_serve_requests", "nanodiloco_loss",
                     "nanodiloco_device_seconds", "nanodiloco_slo_alerts",
                     "nanodiloco_fleet_replicas_serving"):
        assert expected in defs, f"scan lost sight of {expected}"
    assert len(defs) >= 50


def test_family_names_globally_unique(scan):
    """One name, one family: a name defined under two different metric
    types is two families fighting over one exposition line. Same-type
    definitions at multiple sites are allowed (the replica gauge and
    the router's fleet view render the same family about different
    processes)."""
    defs, _labels, _refs = scan
    for name, sites in sorted(defs.items()):
        types = {t for _f, _l, t in sites if t in METRIC_TYPES}
        assert len(types) <= 1, (
            f"{name} is defined as {sorted(types)} at "
            f"{[(f, l) for f, l, _ in sites]} — one family name, one type"
        )


def test_rendered_sample_names_cannot_collide(scan):
    """The exposition renders counters as ``X_total`` and histograms as
    ``X_bucket``/``X_count``/``X_sum``: no family's rendered names may
    collide with another family's. Untyped (gauge-list) definitions
    claim both ``X`` and ``X_total`` — conservative, so an idiom the
    scan cannot type still cannot introduce a collision."""
    defs, _labels, _refs = scan
    rendered: dict[str, str] = {}
    for name, sites in sorted(defs.items()):
        types = {t for _f, _l, t in sites}
        if types == {"untyped"}:
            forms = [name, name + "_total"]
        elif "counter" in types:
            forms = [name + "_total"]
        elif "histogram" in types:
            forms = [name + "_bucket", name + "_count", name + "_sum"]
        else:
            forms = [name]
        for form in forms:
            owner = rendered.get(form)
            assert owner is None or owner == name, (
                f"rendered sample name {form!r} is claimed by BOTH "
                f"{owner} and {name} ({[s[:2] for s in defs[name]]})"
            )
            rendered[form] = name


def test_label_keys_come_from_the_bounded_allowlist(scan):
    """No unbounded-cardinality labels: every label key in every family
    must be in LABEL_ALLOWLIST. A request_id/prompt-derived label mints
    a series per request and melts the collector's ring buffers."""
    defs, labels, _refs = scan
    for name in sorted(labels):
        rogue = labels[name] - LABEL_ALLOWLIST
        assert not rogue, (
            f"{name} (defined at {[s[:2] for s in defs[name]]}) uses "
            f"label key(s) {sorted(rogue)} outside the allowlist "
            f"{sorted(LABEL_ALLOWLIST)} — bounded label sets only; "
            "extending the allowlist is a reviewed decision"
        )


def test_metric_name_references_resolve_to_real_families(scan):
    """Consumer-side references (SLO rules, the autoscaler's forecast
    keys, dashboard section needles) must name a family some producer
    renders — a watcher keyed to a metric nobody emits alarms on
    nothing, forever. Prefix needles (trailing ``_``) and counter
    ``_total`` spellings resolve against the definition set."""
    defs, _labels, refs = scan
    counterish = {
        n for n, sites in defs.items()
        if any(t in ("counter", "untyped") for _f, _l, t in sites)
    }
    bad = []
    for name, rel in refs:
        if name in defs or name in NON_METRIC_NAMES:
            continue
        if name.endswith("_total") and name[:-len("_total")] in counterish:
            continue
        if name.endswith("_"):  # prefix needle (dashboard sections)
            if any(d.startswith(name) for d in defs):
                continue
        bad.append((name, rel))
    assert not bad, (
        f"metric-name references that resolve to NO defined family: "
        f"{sorted(set(bad))}"
    )


# -- span-name lint -----------------------------------------------------------
#
# The trace vocabulary is an operator contract exactly like the metric
# namespace: `report trace` stitches spans emitted by the ROUTER, the
# DISAGG router, and the SERVE scheduler into one tree, and the
# critical-path / waterfall tooling keys on the names. A hop renamed in
# one emitter but not the others silently tears every cross-process
# trace. Same discipline as LABEL_ALLOWLIST: additions need a README
# row (the "Distributed tracing" section) AND an entry here.

SPAN_NAME_ALLOWLIST = {
    # fleet routing (fleet/router.py, fleet/disagg.py)
    "route", "forward", "fallback",
    "handoff", "handoff_prefill", "handoff_export", "handoff_import",
    # serve request phases (serve/scheduler.py)
    "queued", "prefill", "decode", "kv_export", "kv_import",
    # training round phases (training/, parallel/)
    "outer_sync", "ckpt", "data", "cost_analysis", "inner",
    "comm_probe", "sync", "eval", "log",
    # the serving thread's tick, step by step, and the dispatch entries
    # of Diloco (PR 24: trace_span is also a profiler annotation; README
    # "What a capture shows", PERF.md section 3)
    "sched.tick", "sched.control", "sched.expire", "sched.admit",
    "sched.prefill", "sched.deliver", "sched.retire", "sched.idle",
    "engine.start_prefill", "engine.keys", "engine.stage_chunk",
    "engine.prefill_chunk", "engine.draft", "engine.stage",
    "engine.decode_dispatch", "engine.fetch_tokens", "engine.advance",
    "diloco.round", "diloco.outer", "diloco.inner_round",
    # the synthetic root stitch_trace mints for request_id-joined shards
    "trace",
}

# every outcome tag any span may carry — bounded so dashboards and the
# waterfall's outcome coloring can enumerate them. Dynamic outcomes
# (outcome=reason) are constrained at their source: the scheduler's
# finish/drop reasons are all listed here.
SPAN_OUTCOME_ALLOWLIST = {
    "ok", "error", "busy", "unavailable", "shed", "missing",
    "cancelled", "deadline", "deadline_expired", "no_ready_replica",
    "exhausted", "fallback", "stop", "length", "prefilled",
}

_SPAN_CALL_NAMES = {"_span", "span", "trace_span", "record_span"}


def _scan_spans():
    """Every span-emitter call site in the package: ``[(name_or_None,
    outcomes, file, line)]`` — name None when the first argument is not
    a string literal (a variable; its values are someone else's lint),
    outcomes = every string constant inside an ``outcome=`` keyword
    (a conditional expression contributes each of its arms)."""
    sites: list[tuple[str | None, set, str, int]] = []
    for dirpath, _dirs, files in os.walk(PKG):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = (node.func.attr
                         if isinstance(node.func, ast.Attribute)
                         else node.func.id
                         if isinstance(node.func, ast.Name) else None)
                if fname not in _SPAN_CALL_NAMES:
                    continue
                name = None
                if (node.args and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    name = node.args[0].value
                outcomes: set = set()
                for kw in node.keywords:
                    if kw.arg != "outcome":
                        continue
                    for sub in ast.walk(kw.value):
                        if (isinstance(sub, ast.Constant)
                                and isinstance(sub.value, str)):
                            outcomes.add(sub.value)
                if name is not None or outcomes:
                    sites.append((name, outcomes, rel, node.lineno))
    return sites


@pytest.fixture(scope="module")
def span_sites():
    return _scan_spans()


def test_span_scan_finds_the_emitters(span_sites):
    """Sanity pin: the scan sees the known hop names from all three
    emitters (router, disagg, serve scheduler) — if a refactor moves
    span emission to an idiom the scan can't parse, this fails before
    the vocabulary checks silently pass on nothing."""
    names = {n for n, _o, _f, _l in span_sites if n}
    for expected in ("route", "forward", "fallback", "handoff_prefill",
                     "handoff_export", "handoff_import", "queued",
                     "prefill", "decode", "kv_export", "kv_import"):
        assert expected in names, f"span scan lost sight of {expected!r}"


def test_span_names_come_from_the_allowlist(span_sites):
    """One hop vocabulary across every emitter: a span name outside the
    allowlist is either a typo'd rename (which tears `report trace`'s
    cross-process stitch) or a new hop that needs a reviewed allowlist
    entry + README row."""
    bad = [(n, f, l) for n, _o, f, l in span_sites
           if n is not None and n not in SPAN_NAME_ALLOWLIST]
    assert not bad, (
        f"span names outside SPAN_NAME_ALLOWLIST: {sorted(set(bad))} — "
        "hop names are a cross-emitter contract; extending the "
        "allowlist is a reviewed decision"
    )


def test_span_outcomes_come_from_the_allowlist(span_sites):
    """Outcome tags are enumerable: every string an ``outcome=`` kwarg
    can produce (each arm of a conditional counts) must be in the
    bounded allowlist, so waterfall rendering and outcome dashboards
    never meet a tag they can't classify."""
    bad = []
    for name, outcomes, rel, line in span_sites:
        rogue = outcomes - SPAN_OUTCOME_ALLOWLIST
        if rogue:
            bad.append((name, sorted(rogue), rel, line))
    assert not bad, (
        f"span outcome tags outside SPAN_OUTCOME_ALLOWLIST: {bad}"
    )


def test_every_family_documented_in_readme(scan):
    """README's metrics tables are the operator contract: every defined
    family name must appear there. A new family without a table row
    fails HERE, naming itself — documentation is part of adding a
    metric, not a follow-up."""
    defs, _labels, _refs = scan
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    missing = sorted(n for n in defs if n not in readme)
    assert not missing, (
        "families missing from README's metrics tables: "
        + ", ".join(missing)
        + " — add a row (name, type, labels, meaning) to README.md"
    )
