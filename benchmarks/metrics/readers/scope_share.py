"""Device time of the operations under the program's named scopes
(``jax.named_scope``: a component of an operation's scope path in the
trace, the backward pass's ``transpose(jvp(<scope>))`` included), as a
share of the time in which any operation ran. Where the trace carries no
scope path at all, the operations whose name matches ``pattern`` stand
in, if one is given. Returns None where nothing matches."""

import json

from harness import spans as spans_mod


def read(ctx, scopes, pattern=None):
    trace = ctx.get("trace")
    if trace is None or not trace["busy_s"]:
        return None
    loaded = spans_mod.for_cell(ctx)
    seconds, count, how = spans_mod.scope_seconds(
        loaded["device"], scopes, pattern
    )
    if not count:
        return None
    # The trace's session is the window the reduction's busy time is of
    # (averaged over the chips; the operations here are the first's).
    print(json.dumps({"scope_share": {"scopes": scopes, "count": count,
                                      "seconds": seconds, "by": how}}),
          flush=True)
    return 100.0 * seconds / trace["busy_s"]
