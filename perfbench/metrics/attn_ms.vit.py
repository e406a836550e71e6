"""Device milliseconds per unit in the vision tower's attention core: the
leaf ops under the program's ``vit.attn`` scope (scopes.py). Moves
``img_s``.

The tower's blocks run inside a ``lax.scan``, and the attention of a
long image inside a further loop over key chunks: the trace holds an
event for each loop as well as one for each op inside it. So this reads
leaf ops, those no other traced op runs inside, and logs the unit's
device time by part the same way (``device_parts``)."""
import collections

from perfbench import scopes


def _part(path):
    """The innermost ``cim.*`` scope of a path, else its innermost
    ``vit.*`` scope, else ``model``."""
    parts = path.split("/")
    for prefix in ("cim.", "vit."):
        hit = [p for p in parts if p.startswith(prefix)]
        if hit:
            return hit[-1]
    return "model" if path else "(unscoped)"


def leaves(att):
    """{op name: (seconds, scope path)} of the traced ops no other traced
    op runs inside."""
    loops = {up for _, _, up in att.ops.values() if up}
    return {name: (sec, path) for name, (sec, path, _) in att.ops.items()
            if name not in loops}


def read(ctx):
    if ctx.rate != "img_s" or ctx.trace is None or ctx.units <= 0:
        return None
    from repro.obs import names
    scope = getattr(names, "SCOPE_VIT_ATTN", None)
    if scope is None:
        scopes.log("the program declares no vision attention scope")
        return None
    att = scopes.attribute(ctx.trace.ops, scopes.unit_text("attn_ms.vit"))
    found = att.mapped_s + att.nested_s
    every = found + sum(att.unmapped.values())
    if every <= 0 or found / every < scopes.MIN_FOUND:
        scopes.log(f"only {found:.6g}s of {every:.6g}s of the window's ops "
                   f"are in the compiled text: not the program that ran")
        return None
    leaf = leaves(att)
    parts = collections.Counter()
    for sec, path in leaf.values():
        parts[_part(path)] += sec
    scopes.log("device_parts per unit (leaf ops): " + ", ".join(
        f"{k} {1e3 * v / ctx.units:.6g}ms" for k, v in parts.most_common()))
    for name, (sec, path) in sorted(leaf.items(), key=lambda kv: -kv[1][0])[
            :12]:
        scopes.log(f"leaf op {name} {1e3 * sec / ctx.units:.6g}ms a unit: "
                   f"{path or '(unscoped)'}")
    attn = sum(sec for sec, path in leaf.values()
               if scope in path.split("/"))
    return 1e3 * attn / ctx.units if attn > 0 else None
