"""RPL006: collectives bind their axis name to a shard_map context.

``ppermute`` / ``all_gather`` / ``psum`` / ``axis_index`` resolve their
``axis_name`` against the innermost enclosing ``shard_map`` (or vmapped
``spmd_axis_name``) binding; a collective issued outside one fails at
trace time in the best case and silently binds a *different* mesh axis in
the worst (2-D meshes are on the roadmap).  The rule accepts a collective
when either

* it sits lexically inside a function that this module passes to a
  ``shard_map``-family call (``jax.shard_map``, ...) — the binding is
  visible locally; or
* an enclosing function's docstring mentions ``shard_map`` — the
  documented caller-binds contract (e.g. ``halo_exchange_fn``'s closures,
  ``gossip_mix_tree``), which keeps the obligation readable at the def.

Anything else is an unbound collective.
"""

from __future__ import annotations

import ast

from tools.lint.core import FileContext, Rule, dotted_name, register

COLLECTIVES = frozenset(
    {"ppermute", "all_gather", "psum", "pmean", "pmax", "pmin",
     "all_to_all", "axis_index", "pshuffle", "pbroadcast"})

LAX_ROOTS = {"jax", "lax"}


def _collective(call: ast.Call):
    d = dotted_name(call.func)
    if not d:
        return None
    seg = d.split(".")
    if seg[-1] in COLLECTIVES and (seg[0] in LAX_ROOTS or "lax" in seg):
        return d
    return None


def _bound_names(tree) -> set:
    """Function names passed (possibly wrapped) to shard_map-family calls."""
    out = set()

    def harvest(e):
        if isinstance(e, ast.Name):
            out.add(e.id)
        elif isinstance(e, ast.Call):  # e.g. jax.vmap(body), partial(f, ...)
            for a in list(e.args):
                harvest(a)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = dotted_name(node.func) or ""
            if "shard_map" in d.split(".")[-1] and node.args:
                harvest(node.args[0])
    return out


def _scopes(tree):
    """Yield (scope node, enclosing function chain incl. the scope itself
    when it is a function) depth-first; the module is the outermost scope."""
    def visit(node, chain):
        is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        here = chain + (node,) if is_fn else chain
        yield node, here
        for child in ast.iter_child_nodes(node):
            yield from visit(child, here)

    yield from visit(tree, ())


def _own_nodes(scope):
    """Walk a scope's body without descending into nested function defs."""
    todo = list(ast.iter_child_nodes(scope))
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        todo.extend(ast.iter_child_nodes(node))


@register
class AxisBinding(Rule):
    code = "RPL006"
    name = "collective-axis-binding"
    summary = ("collectives run inside a module-visible shard_map body or "
               "under a documented must-run-inside-shard_map contract")

    def check(self, ctx: FileContext):
        bound = _bound_names(ctx.tree)
        for scope, chain in _scopes(ctx.tree):
            if not isinstance(scope, (ast.Module, ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            ok = any(f.name in bound for f in chain) or any(
                "shard_map" in (ast.get_docstring(f) or "").lower()
                for f in chain)
            if ok:
                continue
            for sub in _own_nodes(scope):
                if isinstance(sub, ast.Call):
                    d = _collective(sub)
                    if d:
                        yield ctx.finding(
                            self.code, sub,
                            f"collective `{d}` with no visible shard_map "
                            f"binding — wrap in shard_map here, or "
                            f"document the caller-binds contract in the "
                            f"enclosing docstring")
