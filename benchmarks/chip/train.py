"""The train loop: `CostModelTrainer.run` at dp = the cell's chips, driven
in chunks of steps until the window closes, after three steps from the
seed that the reference follows. The mix's generator module
(`generators/<kind>.py`) gives the corpus and its sampler, the trainer's
task and the reference's loss; each device trains on its own view of the
corpus (`GlobalBatchSampler.for_mesh`), and the reference averages the
shards' losses and gradients as the mesh step's all-reduce does."""
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


BUCKET_FIELDS = ("node_capacity", "edge_capacity", "graph_capacity",
                 "reduce_capacity")


def step_bucket(sampler, step: int) -> tuple:
    """The bucket of the step's global batch, from its shards' own draws:
    each capacity the largest of the shards', as the global sampler
    encodes every shard against one shared bucket."""
    from repro.data.sampler import sparse_draw_spec
    specs = [sparse_draw_spec(s.draw(step)[0]) for s in sampler.samplers]
    return tuple(max(getattr(b, f) for b in specs) for f in BUCKET_FIELDS)


def step_flops(sampler, cfg: dict, step: int) -> float:
    """Training FLOPs of the real (valid) graphs of every shard's draw of
    the step."""
    total = 0.0
    for s in sampler.samplers:
        draw = s.draw(step)
        total += sum(common.train_flops(cfg, g.num_nodes,
                                        len(g.unique_edges()))
                     for g, v in zip(draw[0], draw[-1]) if v)
    return total


def build(cfg: dict, mix: dict, seed: int, devices, fault: dict):
    """The system under test: the generator's corpus, the timed global
    sampler over one view of it per device, and the trainer at dp = the
    number of `devices`, on a (dp, 1) mesh of them."""
    from jax.sharding import Mesh

    from repro.sharding.mesh import DATA_AXIS, MODEL_AXIS
    from repro.training.optim import AdamWConfig
    from repro.training.trainer import CostModelTrainer, TrainerConfig
    dp = len(devices)
    gen = traffic.kind(mix)
    base, norm = gen.corpus(cfg, mix, seed)
    timed = _timed_sampler_class().for_mesh(base, dp)
    timed.fault = fault.get("batch")
    timed.steps, timed.seconds, timed.graphs = [], 0.0, 0
    tc = TrainerConfig(task=gen.TASK, steps=0, ckpt_every=0,
                       log_every=1 << 30, seed=seed, dp=dp,
                       optim=AdamWConfig(**mix["optim"]))
    mesh = Mesh(np.asarray(devices).reshape(dp, 1), (DATA_AXIS, MODEL_AXIS))
    trainer = CostModelTrainer(common.model_config(cfg), tc, timed,
                               mesh=mesh)
    if "step" in fault:
        trainer._train_step = fault["step"](trainer._train_step)
    return gen, base, norm, timed, trainer


def _restart(trainer, cfg: dict, seed: int) -> None:
    """The trainer's state from the seed again, at step 0."""
    from repro.training.optim import adamw_init
    trainer.params = common.make_params(cfg, seed)
    trainer.opt_state = adamw_init(trainer.params)
    trainer.step = 0


def first_steps(trainer, cfg: dict, seed: int, b1: float) -> dict:
    """The three steps from the seed that the reference follows, through
    the trainer's own loop: each step's loss, the first step's gradient
    as the optimizer got it (its first moment over 1 - b1) and the change
    of the weights."""
    import jax
    _restart(trainer, cfg, seed)
    p0 = _host(trainer.params)
    losses = []
    for k in range(1, 4):
        losses.append(float(trainer.run(steps=k, resume=False)["loss"]))
        if k == 1:
            m1 = _host(trainer.opt_state["m"])
    p3 = _host(trainer.params)
    return {"losses": losses,
            "grads": jax.tree_util.tree_map(lambda m: m / (1 - b1), m1),
            "update": jax.tree_util.tree_map(lambda a, b: a - b, p3, p0)}


def check(cfg: dict, mix: dict, gen, base, norm, seed: int, dp: int,
          prog: dict) -> tuple[bool, dict, dict]:
    """The program's first steps against the reference's through the same
    draws: (correct, {number the mix limits: (value, limit)}, every
    number `compare_steps` reads)."""
    import jax.numpy as jnp
    ref = reference_steps(cfg, norm, gen, base, seed, 3, jnp.float32,
                          dict(mix["optim"]), dp)
    got = compare_steps(prog, ref)
    limits = mix["limits"]
    return (all(got[k] <= lim for k, lim in limits.items()),
            {k: (got[k], lim) for k, lim in limits.items()}, got)


def run(ctx) -> dict:
    import jax

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    dp = ctx.chips
    gen, base, norm, timed, trainer = build(cfg, mix, seed, ctx.devices,
                                            ctx.fault)

    # warm-up: one step of every bucket the census of the first
    # `warm_steps` steps meets, through the trainer's own loop
    t_census = time.monotonic()
    first: dict = {}
    for step in range(mix["warm_steps"]):
        first.setdefault(step_bucket(timed, step), step)
    census_s = time.monotonic() - t_census
    # the first step meets weights placed by the initializer, later ones
    # the train step's own output: its bucket is warmed for both
    warm_steps = sorted(first.values())
    warm_steps.insert(0, warm_steps[0])

    class Replay:
        num_shards = dp

        def batch(self, step):
            return timed.batch(warm_steps[step])
    trainer.sampler = Replay()
    _restart(trainer, cfg, seed)
    trainer.run(steps=len(warm_steps), resume=False)
    trainer.sampler = timed

    # the three steps the reference follows, through the same object
    prog = first_steps(trainer, cfg, seed, mix["optim"]["b1"])

    exe0 = ctx.clock.executables
    ctx.counters.update({"warm_buckets": len(warm_steps),
                         "warm_executables": exe0, "census_s": census_s})
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
    correct, checks, gaps = check(cfg, mix, gen, base, norm, seed, dp, prog)

    ctx.counters.update({
        "steps": steps, "traced_steps": traced_steps,
        "graphs": timed.graphs,
        "sampler_seconds": timed.seconds,
        "sampler_steps": len(timed.steps),
        # mfu.train's numerator, read only from a traced run
        "flops": (sum(step_flops(timed, cfg["model"], s)
                      for s in timed.steps) if ctx.trace else 0.0),
        "executables": len(in_window), "window_executables": in_window[:8],
        "gaps": gaps, "check_s": time.monotonic() - t_ref})
    elapsed = t_done - t_start
    ctx.window_s = elapsed
    return {"correct": correct, "attempted": steps, "failed": 0,
            "memory_peak_bytes": memory, "checks": checks,
            "end_to_end": {"train_graphs_per_s": timed.graphs / elapsed}}


def step_inputs(cfg: dict, norm: dict, shards, seed: int, step: int):
    """The reference's view of one step, one entry per shard (device) d:
    d's draw as a dense batch, its targets, groups and valid flags, and
    the dropout keep masks the program draws for it from
    fold_in(key(seed + 1), step * dp + d): over the packed [nodes, hidden]
    layout of the bucket the shards share before the node-final MLP, and
    over its [graphs, reduce rows, hidden] layout after each Transformer
    block's attention, read back per graph."""
    import jax
    import jax.numpy as jnp
    dp = len(shards)
    d = cfg["hidden_dim"]
    keep_p = 1.0 - cfg["dropout"]
    draws = [s.draw(step) for s in shards]
    sizes = [[g.num_nodes for g in draw[0]] for draw in draws]
    # the shared bucket: each capacity the largest of the shards'
    packed = max(_pow2(sum(n), 32) for n in sizes)
    reduce_rows = max(_pow2(max(n), 8) for n in sizes)
    blocks = (cfg["transformer_layers"]
              if cfg["reduction"] == "transformer" else 0)
    out = []
    for shard, (draw, n) in enumerate(zip(draws, sizes)):
        graphs, targets, valid = draw[0], draw[1], draw[-1]
        groups = draw[2] if len(draw) == 4 else np.zeros_like(valid,
                                                              np.int32)
        feats = [reference.featurize(g.to_dict()) for g in graphs]
        rows = reference.pad_rows(max(max(n), 8))
        b = reference.dense_batch(feats, norm, rows)
        rng = jax.random.fold_in(jax.random.key(seed + 1),
                                 step * dp + shard)
        keep = np.asarray(jax.random.bernoulli(
            jax.random.fold_in(rng, 1), keep_p, (packed, d)))
        node_keep = np.zeros((len(graphs), rows, d), bool)
        off = 0
        for i, k in enumerate(n):
            node_keep[i, :k] = keep[off:off + k]
            off += k
        attn_keep = []
        for blk in range(blocks):
            keep = np.asarray(jax.random.bernoulli(
                jax.random.fold_in(rng, blk), keep_p,
                (len(graphs), reduce_rows, d)))
            per_graph = np.zeros((len(graphs), rows, d), bool)
            for i, k in enumerate(n):
                per_graph[i, :k] = keep[i, :k]
            attn_keep.append(jnp.asarray(per_graph))
        arrays = {k: jnp.asarray(v) for k, v in b.items()}
        out.append((arrays, jnp.asarray(node_keep), attn_keep or None,
                    jnp.asarray(targets), jnp.asarray(groups),
                    jnp.asarray(valid)))
    return out


def reference_steps(cfg_file: dict, norm: dict, gen, sampler, seed: int,
                    steps: int, dtype, opt: dict, dp: int = 1) -> dict:
    """The reference's first `steps` optimizer steps from the seed's
    weights on the draws of the sampler's `dp` device views, in `dtype`,
    under the generator's reference loss: each step the mean of the
    shards' losses and gradients, as the mesh step's all-reduce takes it,
    then one AdamW step. Returns the per-step losses, the first step's
    clipped gradient and the change of the weights."""
    import jax
    import jax.numpy as jnp
    cfg = cfg_file["model"]
    static = reference.frozen(cfg)
    shards = [sampler.with_host(d, dp) for d in range(dp)]
    params = reference.cast(common.make_params(cfg_file, seed), dtype)
    p0 = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)

    def loss_fn(p, b, keep, attn_keep, t, g, val):
        preds = reference.forward(p, static, b, keep, attn_keep)
        return gen.reference_loss(preds, t.astype(preds.dtype), g,
                                  val.astype(preds.dtype))

    vg = jax.jit(jax.value_and_grad(loss_fn))
    losses, first = [], None
    with jax.default_matmul_precision("default"):
        for step in range(steps):
            parts = []
            for b, keep, attn_keep, t, g, val in step_inputs(
                    cfg, norm, shards, seed, step):
                parts.append(vg(params, reference.cast(b, dtype), keep,
                                attn_keep, t, g, val))
            loss = sum(p[0] for p in parts) / dp
            grads = jax.tree_util.tree_map(lambda *gs: sum(gs) / dp,
                                           *[p[1] for p in parts])
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


def _cosine_gap(prog, ref) -> float:
    """One less the least cosine, over the leaves, between the program's
    leaf and the reference's."""
    return float(max(
        1.0 - np.vdot(p, r) / max(np.linalg.norm(p) * np.linalg.norm(r),
                                  1e-30)
        for p, r in zip(prog, ref)))


def compare_steps(prog: dict, ref: dict) -> dict:
    """The numbers a train check can compare: the worst relative gap of
    the three steps' losses and that of the first step's alone, the worst
    leaf's gap between the norms of the first gradient and of the change
    of the weights, and the worst leaf's cosine gap of the first gradient.
    A mix's `limits` name those its cell compares."""
    import jax
    flat = jax.tree_util.tree_leaves
    gref = flat(ref["grads"])
    gnorm = np.array([np.linalg.norm(x) for x in gref])
    keep = gnorm >= 1e-3 * np.median(gnorm)
    pick = lambda xs: [x for x, k in zip(xs, keep) if k]  # noqa: E731
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                ref["losses"])]
    grads = pick(flat(prog["grads"])), pick(gref)
    return {"loss_gap": float(max(loss)), "first_loss_gap": float(loss[0]),
            "grad_gap": _leaf_gap(*grads),
            "grad_cosine_gap": _cosine_gap(*grads),
            "update_gap": _leaf_gap(pick(flat(prog["update"])),
                                    pick(flat(ref["update"])))}
