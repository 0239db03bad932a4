"""obs-discipline: one observability surface, no ad-hoc side channels.

PR 9 replaced hand-rolled ``stats`` dicts with the typed
``obs.MetricsRegistry`` and made ``_finalize_stats`` the single assembly
point of ``last_stats``.  This rule keeps it that way:

* metric names registered on a ``counter`` / ``gauge`` / ``timer`` /
  ``histogram`` must parse: lowercase ``[a-z0-9_]`` segments, and a
  slashed name's namespace must be one of the known surfaces
  (``rollout/``, ``tool/``, ``train/``, ``reward/``, ``engine/``,
  ``compile/``, …) —
  a typo'd namespace silently forks the metric off every dashboard;
* a *bare* (unslashed) name is only meaningful on a child registry that
  forwards under a ``parent_prefix`` — modules that never construct one
  get flagged;
* ``last_stats`` is written only by ``_finalize_stats`` (re-exporting a
  finalized dict — assignment from a call — is fine anywhere);
* no new ad-hoc stats dicts: a non-empty dict literal assigned to an
  attribute named ``stats`` / ``*_stats``, or subscript-mutated, is the
  pattern the registry replaced.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, Sequence

from repro.analysis.engine import Finding, Module
from repro.analysis.rules.common import (dotted_name,
                                         enclosing_function_names,
                                         iter_calls, str_arg)

DEFAULT_NAMESPACES = ("rollout", "tool", "train", "reward", "engine",
                      "eval", "dryrun", "compile")
_SEGMENT_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_INSTRUMENT_FACTORIES = {"counter", "gauge", "timer", "histogram"}
_FINALIZERS = ("_finalize_stats",)


class ObsDisciplineRule:
    name = "obs-discipline"
    description = ("metric names must parse against the known namespaces; "
                   "last_stats is only assembled in _finalize_stats; no "
                   "ad-hoc stats dicts")

    def __init__(self, namespaces: Sequence[str] = DEFAULT_NAMESPACES):
        self.namespaces = frozenset(namespaces)

    # ------------------------------------------------------------ helpers
    def _has_prefixed_child_registry(self, module: Module) -> bool:
        """Does this module build a ``MetricsRegistry(parent_prefix=…)``
        child?  Bare instrument names are legitimate only there."""
        for call in iter_calls(module.tree):
            if dotted_name(call.func).rsplit(".", 1)[-1] != "MetricsRegistry":
                continue
            for kw in call.keywords:
                if kw.arg == "parent_prefix":
                    return True
        return False

    # ------------------------------------------------------------ checks
    def check(self, module: Module) -> Iterator[Finding]:
        yield from self._check_metric_names(module)
        yield from self._check_last_stats(module)
        yield from self._check_adhoc_stats(module)

    def _check_metric_names(self, module: Module) -> Iterator[Finding]:
        has_child = None        # lazy: most modules register nothing
        for call in iter_calls(module.tree):
            if not isinstance(call.func, ast.Attribute) \
                    or call.func.attr not in _INSTRUMENT_FACTORIES:
                continue
            name = str_arg(call, 0)
            if name is None:
                continue        # dynamic name: out of scope for a linter
            segments = name.split("/")
            if any(not _SEGMENT_RE.match(s) for s in segments):
                yield module.finding(
                    self.name, call,
                    f"metric name {name!r} does not parse: segments must "
                    "match [a-z][a-z0-9_]*, separated by '/'")
                continue
            if len(segments) > 1:
                if segments[0] not in self.namespaces:
                    yield module.finding(
                        self.name, call,
                        f"metric namespace {segments[0]!r} (in {name!r}) is "
                        f"not a known surface "
                        f"({'/, '.join(sorted(self.namespaces))}/) — a "
                        "typo'd namespace forks the metric off every "
                        "dashboard")
            else:
                if has_child is None:
                    has_child = self._has_prefixed_child_registry(module)
                if not has_child:
                    yield module.finding(
                        self.name, call,
                        f"bare metric name {name!r} outside a parent_prefix "
                        "child registry: it lands un-namespaced in the "
                        "process snapshot — prefix it (e.g. "
                        "'rollout/…') or record it on a child registry")

    def _is_last_stats_attr(self, node) -> bool:
        return isinstance(node, ast.Attribute) and node.attr == "last_stats"

    def _check_last_stats(self, module: Module) -> Iterator[Finding]:
        enclosing = enclosing_function_names(module.tree)

        def in_finalizer(node) -> bool:
            return any(n in _FINALIZERS
                       for n in enclosing.get(id(node), ()))

        for node in ast.walk(module.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    # self.last_stats[…] = … / += …
                    if isinstance(t, ast.Subscript) \
                            and self._is_last_stats_attr(t.value) \
                            and not in_finalizer(node):
                        yield module.finding(
                            self.name, node,
                            "direct last_stats mutation outside "
                            "_finalize_stats: every exit path must report "
                            "the same key set — add the key there instead")
                    # self.last_stats = {…non-empty literal…}
                    elif self._is_last_stats_attr(t) \
                            and isinstance(node, ast.Assign) \
                            and isinstance(node.value, ast.Dict) \
                            and node.value.keys \
                            and not in_finalizer(node):
                        yield module.finding(
                            self.name, node,
                            "last_stats assembled ad hoc outside "
                            "_finalize_stats — route it through the "
                            "finalizer so the key set stays uniform")
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("update", "setdefault", "pop",
                                           "clear") \
                    and self._is_last_stats_attr(node.func.value) \
                    and not in_finalizer(node):
                yield module.finding(
                    self.name, node,
                    f"last_stats.{node.func.attr}() outside _finalize_stats "
                    "— every exit path must report the same key set")

    def _check_adhoc_stats(self, module: Module) -> Iterator[Finding]:
        def is_stats_attr(node) -> bool:
            return (isinstance(node, ast.Attribute)
                    and node.attr != "last_stats"
                    and (node.attr == "stats" or node.attr.endswith("_stats")))

        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if is_stats_attr(t) and isinstance(node.value, ast.Dict) \
                            and node.value.keys:
                        yield module.finding(
                            self.name, node,
                            f"ad-hoc stats dict assigned to "
                            f"{t.attr!r}: use obs.MetricsRegistry "
                            "instruments (keep a read-only dict *view* if "
                            "legacy consumers need one)")
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Subscript) \
                    and is_stats_attr(node.target.value):
                yield module.finding(
                    self.name, node,
                    f"ad-hoc stats dict mutation "
                    f"({node.target.value.attr!r}[…] += …): use a typed "
                    "instrument on obs.MetricsRegistry")
