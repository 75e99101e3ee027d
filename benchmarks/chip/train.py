"""The train loop: `CostModelTrainer.run` driven in chunks of steps until
the window closes, after three steps from the seed that the reference
follows. The mix's generator module (`generators/<kind>.py`) gives the
corpus and its sampler, the trainer's task and the reference's loss."""
from __future__ import annotations

import time

import numpy as np

import common
import reference
import traffic


def _timed_sampler_class():
    from repro.data.sampler import GlobalBatchSampler

    class TimedSampler(GlobalBatchSampler):
        """The mesh trainer's global sampler with a host clock around each
        `batch(step)`, counting the steps and real graphs of the window."""

        live = False
        fault = None

        def batch(self, step: int):
            import jax
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.sampler"):
                b = super().batch(step)
            dt = time.perf_counter() - t0
            if self.fault is not None:
                b = self.fault(b)
            if self.live:
                self.steps.append(step)
                self.seconds += dt
                self.graphs += int(np.count_nonzero(b.valid))
            return b

    return TimedSampler


def _pow2(n: int, lo: int) -> int:
    return 1 << (max(n, lo) - 1).bit_length()


def _host(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: np.array(x, np.float64), tree)


def step_bucket(sampler, step: int):
    """The bucket of the step's pack, from the sampler's own draw."""
    from repro.data.sampler import sparse_draw_spec
    return sparse_draw_spec(sampler.draw(step)[0])


def step_flops(sampler, cfg: dict, step: int) -> float:
    """Training FLOPs of the real (valid) graphs of the step's draw."""
    graphs, _, _, valid = sampler.draw(step)
    return float(sum(common.train_flops(cfg, g.num_nodes,
                                        len(g.unique_edges()))
                     for g, v in zip(graphs, valid) if v))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.training.optim import AdamWConfig, adamw_init
    from repro.training.trainer import CostModelTrainer, TrainerConfig

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    mcfg = cfg["model"]
    if ctx.chips != 1:
        raise SystemExit("this train loop and its reference step run on "
                         "one chip")
    gen = traffic.kind(mix)
    mc = common.model_config(cfg)
    opt = dict(mix["optim"])

    base, norm = gen.corpus(cfg, mix, seed)
    timed = _timed_sampler_class()([base])
    timed.fault = ctx.fault.get("batch")
    timed.steps, timed.seconds, timed.graphs = [], 0.0, 0
    tc = TrainerConfig(task=gen.TASK, steps=0, ckpt_every=0,
                       log_every=1 << 30, seed=seed, dp=ctx.chips,
                       optim=AdamWConfig(**opt))
    trainer = CostModelTrainer(mc, tc, timed)
    if "step" in ctx.fault:
        trainer._train_step = ctx.fault["step"](trainer._train_step)

    # warm-up: one step of every bucket the window can meet, through the
    # trainer's own loop, then the state from the seed again
    first: dict = {}
    for step in range(mix["warm_steps"]):
        first.setdefault(step_bucket(base, step), step)
    # the first step meets weights placed on one device, later ones the
    # mesh's replicated weights: its bucket is warmed for both
    warm_steps = sorted(first.values())
    warm_steps.insert(0, warm_steps[0])

    class Replay:
        num_shards = 1

        def batch(self, step):
            return timed.batch(warm_steps[step])
    trainer.sampler = Replay()
    trainer.params = common.make_params(cfg, seed)
    trainer.opt_state = adamw_init(trainer.params)
    trainer.run(steps=len(warm_steps), resume=False)
    trainer.sampler = timed
    trainer.params = common.make_params(cfg, seed)
    trainer.opt_state = adamw_init(trainer.params)
    trainer.step = 0

    # the three steps the reference follows, through the same object
    p0 = _host(trainer.params)
    losses = []
    for k in range(1, 4):
        losses.append(float(trainer.run(steps=k, resume=False)["loss"]))
        if k == 1:
            m1 = _host(trainer.opt_state["m"])
    p3 = _host(trainer.params)

    exe0 = ctx.clock.executables
    ctx.counters.update({"warm_buckets": len(warm_steps),
                         "warm_executables": exe0})
    t_start = time.monotonic()
    ctx.begin_window(t_start)
    t_start = time.monotonic()
    t_end = t_start + ctx.seconds
    timed.live = True
    traced_steps = None
    while time.monotonic() < t_end:
        trainer.run(steps=trainer.step + mix["chunk_steps"], resume=False)
        if traced_steps is None and time.monotonic() >= ctx.trace_end:
            jax.block_until_ready(trainer.params)
            ctx.end_trace()
            traced_steps = trainer.step - 3
    jax.block_until_ready(trainer.params)
    t_done = time.monotonic()
    timed.live = False
    in_window = ctx.clock.names[exe0:]
    if traced_steps is None:
        ctx.end_trace()
        traced_steps = trainer.step - 3
    steps = trainer.step - 3
    memory = common.memory_peak(ctx.devices)
    del trainer

    # the check: the reference through the same three draws
    t_ref = time.monotonic()
    ref = reference_steps(cfg, norm, gen, base, seed, 3, jnp.float32, opt)
    b1 = opt["b1"]
    prog = {"losses": losses,
            "grads": jax.tree_util.tree_map(lambda m: m / (1 - b1), m1),
            "update": jax.tree_util.tree_map(lambda a, b: a - b, p3, p0)}
    checks_v = compare_steps(prog, ref)
    limits = mix["limits"]
    checks = {k: (v, limits[k]) for k, v in checks_v.items()}
    correct = all(v <= limits[k] for k, v in checks_v.items())

    ctx.counters.update({
        "steps": steps, "traced_steps": traced_steps,
        "sampler_seconds": timed.seconds,
        "sampler_steps": len(timed.steps),
        # mfu.train's numerator, read only from a traced run
        "flops": (sum(step_flops(base, mcfg, s) for s in timed.steps)
                  if ctx.trace else 0.0),
        "executables": len(in_window), "window_executables": in_window[:8],
        "check_s": time.monotonic() - t_ref})
    elapsed = t_done - t_start
    ctx.window_s = elapsed
    return {"correct": correct, "attempted": steps, "failed": 0,
            "memory_peak_bytes": memory, "checks": checks,
            "end_to_end": {"train_graphs_per_s": timed.graphs / elapsed}}


def step_inputs(cfg: dict, norm: dict, sampler, seed: int, step: int):
    """The reference's view of one step: the draw as a dense batch, its
    targets, groups and valid flags, and the dropout keep mask the program
    draws for it (jax.random over the packed [nodes, hidden] layout of the
    step, read back per graph)."""
    import jax
    import jax.numpy as jnp
    graphs, targets, groups, valid = sampler.draw(step)
    feats = [reference.featurize(g.to_dict()) for g in graphs]
    sizes = [f["opcodes"].shape[0] for f in feats]
    rows = reference.pad_rows(max(max(sizes), 8))
    b = reference.dense_batch(feats, norm, rows)
    d = cfg["hidden_dim"]
    rng = jax.random.fold_in(jax.random.key(seed + 1), step)
    packed = _pow2(sum(sizes), 32)
    keep = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(rng, 1), 1.0 - cfg["dropout"], (packed, d)))
    node_keep = np.zeros((len(graphs), rows, d), bool)
    off = 0
    for i, n in enumerate(sizes):
        node_keep[i, :n] = keep[off:off + n]
        off += n
    arrays = {k: jnp.asarray(v) for k, v in b.items()}
    return arrays, jnp.asarray(node_keep), jnp.asarray(targets), \
        jnp.asarray(groups), jnp.asarray(valid)


def reference_steps(cfg_file: dict, norm: dict, gen, sampler, seed: int,
                    steps: int, dtype, opt: dict) -> dict:
    """The reference's first `steps` optimizer steps from the seed's
    weights on the sampler's draws, in `dtype`, under the generator's
    reference loss: per-step losses, the first step's clipped gradient and
    the change of the weights."""
    import jax
    import jax.numpy as jnp
    cfg = cfg_file["model"]
    static = reference.frozen(cfg)
    params = reference.cast(common.make_params(cfg_file, seed), dtype)
    p0 = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)

    def loss_fn(p, b, keep, t, g, val):
        preds = reference.forward(p, static, b, keep)
        return gen.reference_loss(preds, t.astype(preds.dtype), g,
                                  val.astype(preds.dtype))

    vg = jax.jit(jax.value_and_grad(loss_fn))
    losses, first = [], None
    with jax.default_matmul_precision("default"):
        for step in range(steps):
            b, keep, t, g, val = step_inputs(cfg, norm, sampler, seed, step)
            b = reference.cast(b, dtype)
            loss, grads = vg(params, b, keep, t, g, val)
            params, m, v, clipped = reference.adamw(
                params, grads, m, v, step + 1, opt)
            losses.append(float(loss))
            if first is None:
                first = clipped
    return {"losses": losses, "grads": _host(first),
            "update": _host(jax.tree_util.tree_map(lambda a, b: a - b,
                                                   params, p0))}


def _leaf_gap(prog, ref) -> float:
    """Worst leaf's gap between the norms, over the larger of that leaf's
    reference norm and the median leaf's; leaves whose reference gradient
    is under a thousandth of the median leaf's are left out by the
    caller."""
    pn = np.array([np.linalg.norm(x) for x in prog])
    rn = np.array([np.linalg.norm(x) for x in ref])
    floor = np.median(rn)
    return float(np.max(np.abs(pn - rn) / np.maximum(rn, floor)))


def compare_steps(prog: dict, ref: dict) -> dict:
    import jax
    flat = jax.tree_util.tree_leaves
    gref = flat(ref["grads"])
    gnorm = np.array([np.linalg.norm(x) for x in gref])
    keep = gnorm >= 1e-3 * np.median(gnorm)
    pick = lambda xs: [x for x, k in zip(xs, keep) if k]  # noqa: E731
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    return {"loss_gap": float(loss),
            "grad_gap": _leaf_gap(pick(flat(prog["grads"])), pick(gref)),
            "update_gap": _leaf_gap(pick(flat(prog["update"])),
                                    pick(flat(ref["update"])))}
