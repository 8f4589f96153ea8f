"""Collective discipline (DESIGN.md §9, PR 5).

The measured trap: a collective per loop iteration. A partitioner-sharded
vmapped while_loop all-reduces EVERY iteration — ~60x slower than the
fan-out that keeps lanes independent (PR 5, measured on a CPU host mesh).
The audited counterexamples (CG with one psum per matvec in
core/distributed.py, the pipeline's per-tick ppermute) are excluded by
pyproject scoping or carry inline justifications."""
from __future__ import annotations

import ast

from ..registry import RawFinding, Rule, RuleMeta, register
from ._common import COLLECTIVES, loop_bodies


@register
class CollectiveInLoopBody(Rule):
    """COL001: psum/all_gather/ppermute lexically inside a
    while_loop/fori_loop/scan body."""

    meta = RuleMeta(
        id="COL001", name="collective-in-loop-body",
        summary="no collectives inside lax loop bodies outside audited "
                "modules (~60x trap, PR 5)",
        # core/distributed.py is the audited home of per-iteration
        # collectives (one psum per CG matvec, priced by the router);
        # the repo pyproject also lists it, this default keeps fixture
        # runs faithful without a config.
        default_exclude=("src/repro/core/distributed.py",))

    def check(self, ctx):
        seen = set()
        for body, loop_call, loop_name in loop_bodies(ctx):
            for sub in ast.walk(body):
                if isinstance(sub, ast.Call):
                    cname = ctx.resolve(sub.func)
                    if cname in COLLECTIVES and id(sub) not in seen:
                        seen.add(id(sub))
                        yield RawFinding(
                            sub.lineno, sub.col_offset,
                            f"`{cname.rsplit('.', 1)[-1]}` inside a "
                            f"`{loop_name.rsplit('.', 1)[-1]}` body (line "
                            f"{loop_call.lineno}) pays one all-reduce per "
                            "iteration — hoist it, or justify the schedule "
                            "with a suppression (measured ~60x, DESIGN.md §9)")


@register
class ShardMapNeedsMesh(Rule):
    """COL002: `shard_map` without an explicit mesh.

    Mesh-less shard_map falls back to ambient/abstract-mesh context; the
    repo's routing layer prices meshes explicitly, so every shard_map call
    names the mesh it spans (positionally or `mesh=`).
    """

    meta = RuleMeta(
        id="COL002", name="shardmap-needs-mesh",
        summary="shard_map always passes its mesh explicitly")

    def check(self, ctx):
        for call in ctx.calls():
            name = ctx.resolve(call.func)
            if not name or not name.endswith("shard_map"):
                continue
            has_mesh = (len(call.args) >= 2
                        or any(kw.arg == "mesh" for kw in call.keywords))
            if not has_mesh:
                yield RawFinding(
                    call.lineno, call.col_offset,
                    "`shard_map` without an explicit mesh argument — name "
                    "the mesh (routing prices it; DESIGN.md §9.5)")
