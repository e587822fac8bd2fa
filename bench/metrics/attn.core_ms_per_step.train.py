"""attn.core_ms_per_step.train: device time of attention's core per step.

Device milliseconds per training step of the operations in the model scope
``attn_core`` (scores, mask, softmax, weighted sum), forward, backward and
rematerialized, in the traced window.  Each operation is mapped to its
scope through the ``op_name`` metadata of the phase program that ran it
(``harness.attribution``: the half before the window's swap with the
phase-0 program, the half after with the phase-1 program).  An operation
counts when its own metadata names the scope: its ``op_name``, or that of
the computation it calls (a fusion's body); one whose scope is only guessed
from its neighbours (``near``) does not.  Standard error gets the whole
table: milliseconds per step of every scope by how it was found (own,
called, near), the shares of each over the device-op time, the unscoped
remainder by operation, and the seconds the programs took to compile for
their maps.  Nothing is read when the program opens no scopes.  Moves
``train_tokens_per_s``.
"""

import sys

from harness import attribution


def read(ctx):
    r, tr = ctx.readings, ctx.trace_data
    if tr is None or "steps" not in r:
        return None
    parts = attribution.halves(tr, ctx.window_ns)
    if parts is None:
        return None
    maps = attribution.train_scope_maps(ctx)
    if maps is None:
        return None
    table = attribution.scope_time(
        tr, [(parts[0], maps[0]), (parts[1], maps[1])])
    split = attribution.sum_by(table, 0, 1)
    n = r["steps"][0] + r["steps"][1]
    core = split.get(("attn_core", "own"), 0.0) + \
        split.get(("attn_core", "called"), 0.0)
    if n <= 0 or core <= 0:
        return None
    total = sum(table.values())
    by_how = attribution.sum_by(table, 1)
    scopes = sorted(attribution.sum_by(
        {k: v for k, v in table.items() if k[0] is not None}, 0).items(),
        key=lambda kv: -kv[1])
    rows = ", ".join(
        f"{sc} " + " / ".join(f"{1e3 * split.get((sc, h), 0.0) / n:.3f}"
                              for h in attribution.HOW)
        for sc, _ in scopes)
    rest = attribution.sum_by(
        {k: v for k, v in table.items() if k[0] is None}, 2)
    top = ", ".join(f"{k} {1e3 * v / n:.3f}" for k, v in
                    sorted(rest.items(), key=lambda kv: -kv[1])[:8])
    shares = ", ".join(f"{h or 'unscoped'} "
                       f"{100 * by_how.get(h, 0.0) / total:.2f}%"
                       for h in attribution.HOW + (None,))
    print(f"attn.core_ms_per_step.train: ms per step over {n} steps by "
          f"scope, own / called / near: {rows}; of {total:.3f} s device-op "
          f"time {shares}: {top}; maps compiled in "
          f"{ctx.scope_maps_s:.1f} s", file=sys.stderr)
    return 1e3 * core / n
