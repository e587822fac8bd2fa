"""Kind ``train_job``: one chip's share of a sequential-freezing training job.

Parameters (the traffic file): ``global_batch`` and ``seq_len`` of every
step, and ``remat``, the program's rematerialization setting for the job.
Batches come from the program's own ``LMBatchIterator`` seeded from
``--seed``; steps are dispatched as ``launch/train.py`` dispatches them:
host batch, ``shard_batch``, the compiled phase step, the loss read back.

Set-up builds one train state from the benchmark's weights and compiles
both phase programs.  It drives that state through the first three steps
through the window's own call (two in phase 0, the Algorithm-2 swap, one in
phase 1), reading what the correctness check needs, swaps back, and runs
one more phase-0 step.  The window is two halves of ``--seconds / 2``:
phase 0 (u frozen), then the swap, timed apart, then phase 1 (v frozen).
``train_tokens_per_s`` is every token of both halves over their time.

After the window the state is freed and the float32 reference runs the
same three steps from the same weights and batches.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import checks, core, program, reference, weights

CHECK_STEPS = (0, 0, 1)  # phases of the steps the reference follows


def _paths_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return (["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat], [x for _, x in flat])


@jax.jit
def _sq_list(xs):
    return [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in xs]


def sq_norms(tree) -> dict:
    paths, leaves = _paths_leaves(tree)
    return dict(zip(paths, (float(v) for v in jax.device_get(_sq_list(leaves)))))


@functools.lru_cache(maxsize=None)
def _change_fn(specs):
    """Squared norms of (leaf - its initial value made again from the
    seed), for leaves given by (path, index, shape, dtype)."""
    def f(key, xs):
        out = []
        for (path, i, shape, dtype), x in zip(specs, xs):
            p0 = weights.leaf_value(key, i, path, shape, dtype)
            d = x.astype(jnp.float32) - p0.astype(jnp.float32)
            out.append(jnp.sum(d * d))
        return out
    return jax.jit(f)


def change_sq(tree, index: dict, seed: int) -> dict:
    paths, leaves = _paths_leaves(tree)
    specs = tuple((p,) + index[p] for p in paths)
    vals = jax.device_get(_change_fn(specs)(weights.seed_key(seed), leaves))
    return dict(zip(paths, (float(v) for v in vals)))


def run(ctx: core.Context) -> None:
    from repro.data import LMBatchIterator
    from repro.kernels import ops
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh

    cfg, job = ctx.config, ctx.traffic
    run_cfg = program.train_run(cfg, job, interpret=ctx.interpret,
                                smoke_model=ctx.smoke_model)
    mesh = make_host_mesh(1, 1)
    abstract = program.abstract_params(run_cfg)
    index = weights.leaf_index(abstract)
    b, s = job["global_batch"], job["seq_len"]
    tokens_per_step = b * s

    params = weights.make_params(abstract, ctx.seed)
    state, parked = steps.make_sharded_train_state(run_cfg, params, 0, mesh)
    del params
    data_seed = int(ctx.seed) % (2 ** 31)
    it = iter(LMBatchIterator(run_cfg.model.vocab_size, s, b,
                              seed=data_seed))

    def feed():
        with core.span("bench.batch"):
            return steps.shard_batch(next(it), mesh)

    def step(prog, state):
        batch = feed()
        with core.span("bench.train_step"):
            state, metrics = prog(state, batch)
            loss = float(metrics["loss"])
        return state, loss

    def swap(state, parked, phase):
        with core.span("bench.swap"):
            t0 = time.perf_counter()
            state, parked = steps.repartition_state(
                run_cfg.optim, state, parked, phase, mesh=mesh, run=run_cfg)
            jax.block_until_ready(state)
            return state, parked, time.perf_counter() - t0

    fb_before = program.fallback_total()
    train_step = steps.build_train_step(run_cfg, mesh)
    first = steps.shard_batch(_zeros_batch(b, s), mesh)
    with ops.capture_fallbacks() as fell:
        prog = {0: steps.compile_train_step(run_cfg, mesh, train_step, state,
                                            first, 0)}
    del first

    # the first three steps, through the window's own call
    losses = []
    u_before = change_sq(state.frozen, index, ctx.seed)
    state, loss = step(prog[0], state)
    losses.append(loss)
    grad_sq = sq_norms(state.opt.mu)  # SGD momentum after one step: g1
    state, loss = step(prog[0], state)
    losses.append(loss)
    u_after_2 = change_sq(state.frozen, index, ctx.seed)
    state, parked, _ = swap(state, parked, 1)
    with ops.capture_fallbacks() as fell1:
        prog[1] = steps.compile_train_step(
            run_cfg, mesh, train_step, state,
            steps.shard_batch(_zeros_batch(b, s), mesh), 1)
    v_after_2 = change_sq(state.frozen, index, ctx.seed)
    state, loss = step(prog[1], state)
    losses.append(loss)
    changed = change_sq(state.params, index, ctx.seed)
    v_after_3 = change_sq(state.frozen, index, ctx.seed)
    state, parked, _ = swap(state, parked, 0)
    state, _ = step(prog[0], state)

    ctx.readings.update(
        fallbacks=program.fallback_total() - fb_before,
        fallback_shapes=[(f.op, f.shape) for f in list(fell) + list(fell1)],
        abstract=abstract, tokens_per_step=tokens_per_step, seq_len=s,
        remat=run_cfg.dist.remat != "none")
    ctx.setup_done()

    # the window
    steps_done = {0: 0, 1: 0}
    half_s = {0: 0.0, 1: 0.0}
    bad = 0
    with core.Window(ctx):
        for phase in (0, 1):
            if phase:
                state, parked, swap_s = swap(state, parked, 1)
                ctx.readings["swap_s"] = swap_s
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < ctx.seconds / 2:
                state, loss = step(prog[phase], state)
                steps_done[phase] += 1
                bad += not np.isfinite(loss)
            half_s[phase] = time.perf_counter() - t0
    total = steps_done[0] + steps_done[1]
    ctx.attempted, ctx.failed = total, bad
    ctx.e2e["train_tokens_per_s"] = total * tokens_per_step / max(
        half_s[0] + half_s[1], 1e-9)
    ctx.readings.update(steps=steps_done, half_s=half_s)
    ctx.memory_peak_bytes = core.memory_peak()
    del state, parked, prog
    core.free_device_memory()

    compare(ctx, abstract, index, losses, grad_sq, changed,
            [(u_after_2, u_before), (v_after_3, v_after_2)],
            run_cfg.model.vocab_size, b, s, data_seed)


def _zeros_batch(b, s):
    """A batch of the job's shape for compiling."""
    return {"tokens": np.zeros((b, s), np.int32),
            "labels": np.zeros((b, s), np.int32)}


def reference_readings(ctx, abstract, index, vocab, b, s, data_seed,
                       mode: str = "f32", rows_kept=None) -> dict:
    """The reference's losses, first-gradient norms and changes over the
    checked steps.  ``mode="fp8"`` is the control; ``rows_kept`` keeps
    only that many rows of each batch (a planted fault)."""
    from repro.data import LMBatchIterator
    it = iter(LMBatchIterator(vocab, s, b, seed=data_seed))
    batches = [next(it) for _ in CHECK_STEPS]
    if rows_kept is not None:
        batches = [{k: v[:rows_kept] for k, v in bt.items()}
                   for bt in batches]
    spec = reference.Spec.from_config(ctx.config, mode=mode)
    params0 = weights.make_params(abstract, ctx.seed)
    rows = min(1024, batches[0]["tokens"].size)
    ref = reference.train_steps(spec, params0, batches, CHECK_STEPS,
                                ctx.config["optimizer"], rows=rows)
    del params0
    ref["change_sq"] = change_sq(ref.pop("params"), index, ctx.seed)
    return ref


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of program readings ``prog`` (``losses``,
    ``first_grad_sq``, ``change_sq``, ``frozen``) against ``ref``."""
    moving = checks.moving_leaves(ref["trained_grad_sq"])
    # a frozen leaf's distance from its initial value, read by the same
    # program before and after its frozen steps, must not change at all
    frozen_moved = sum(1 for after, before in prog.get("frozen", ())
                       for p, v in after.items() if v != before.get(p))
    return {
        "loss_gap": max(abs(a - r) for a, r in zip(prog["losses"],
                                                   ref["losses"])),
        "grad_gap": checks.norm_gap(prog["first_grad_sq"],
                                    ref["first_grad_sq"]),
        "change_gap": checks.norm_gap(prog["change_sq"], ref["change_sq"],
                                      keep=moving),
        "frozen_moved": frozen_moved,
    }


def compare(ctx, abstract, index, losses, grad_sq, changed, frozen,
            vocab, b, s, data_seed) -> None:
    """Run the reference over the three checked steps and record the
    checks."""
    prog = {"losses": losses, "first_grad_sq": grad_sq, "change_sq": changed,
            "frozen": frozen}
    ref = reference_readings(ctx, abstract, index, vocab, b, s, data_seed)
    for name, value in numbers(prog, ref).items():
        ctx.check(name, value)
    ctx.readings.update(program=prog, reference=ref,
                        ref_args=(abstract, index, vocab, b, s, data_seed))


def controls(ctx, seed) -> list:
    """The control and the planted faults, each against the float32
    reference of ``ctx``'s run: rows of compared numbers."""
    ref = ctx.readings["reference"]
    args = ctx.readings["ref_args"]
    b = args[3]
    rows = []
    for what, kw in (("control_fp8", {"mode": "fp8"}),
                     ("fault_half_batch", {"rows_kept": b // 2})):
        if what == "fault_half_batch" and b < 2:
            continue
        t0 = time.perf_counter()
        got = reference_readings(ctx, *args, **kw)
        got["frozen"] = ()
        rows.append({"seed": seed, "what": what,
                     "s": time.perf_counter() - t0, **numbers(got, ref)})
    return rows
