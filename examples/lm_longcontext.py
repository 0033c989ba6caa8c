"""Long-context LM training: store-fed token windows, dp×sp mesh, ring
attention, rematerialized blocks.

The capability showcase the reference cannot express (no sequence
dimension at all, SURVEY §2.2): sequences are sharded across the ``sp``
mesh axis so per-device activation memory is O(S/n), K/V chunks rotate
over the interconnect inside ring attention, and ``--remat`` trades
recompute for the rest of the activation memory. Token windows live in
the distributed store and stream through the prefetching loader straight
into the dp×sp sharding the step demands: natural order, one contiguous
chunk of every window an sp position. Inside the step the loss lays the
token ids, targets and positions out in the ring's balanced order (sp
position i works on stripes i and 2·sp-1-i of the window, so the causal
mask costs every position the same); the first epoch prints what each
position computes (``ring_geometry``).

Run single-process (8 virtual devices, 2×4 dp×sp):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm_longcontext.py --seq 2048 --epochs 2

Multi-process works exactly like the other examples (DDSTORE_RANK/WORLD/
RDV_DIR env; the store goes over TCP). ``--accum-steps N`` trains the
same effective batch in 1/N the activation memory (gradient
accumulation); ``--generate N`` ends the run with a KV-cached greedy
continuation of a training window's prefix (one-pass prompt prefill).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def expert_rows(loads) -> str:
    """What the epoch's load vectors (steps, expert layers, experts) say of
    the expert layers: the last step's largest over mean load, the share
    of layer-steps whose held pairs fit one trip over the sorted rows
    (``counters()["moe_layout"]``: every layer of a model has the same),
    the held pairs over the rows computed for them, and over the layer's
    ``T k`` pairs, mean over layers and steps: the share of the pairs'
    rows the way back (``ddstore_moe_combine``) read."""
    import numpy as np

    from ddstore_tpu.utils import profile

    lay = next(iter(profile.counters()["moe_layout"].values()))
    rows = lay["rows"]
    live = loads[..., lay["first"]:lay["first"] + lay["held"]].sum(-1)
    trips = np.maximum(1, -(-live // rows))
    return (f" expert load max/mean="
            f"{float((loads[-1].max(-1) / loads[-1].mean(-1)).max()):.2f}"
            f" one trip of {rows} sorted rows (of "
            f"{lay['tokens'] * lay['top_k']}) in "
            f"{float((trips == 1).mean()):.3f} of layer-steps,"
            f" live/computed rows={live.sum() / (trips * rows).sum():.3f}"
            f" way back ({lay['combine']}) read "
            f"{float(live.mean()) / (lay['tokens'] * lay['top_k']):.4f}"
            f" of the T k pairs' rows")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--windows", type=int, default=256,
                   help="token windows per process shard")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (>1 selects the pipelined train "
                        "step; composes with dp, sp and --tp)")
    p.add_argument("--tp", type=int, default=1,
                   help="megatron tensor-parallel axis size (composes "
                        "with --pp: stage stacks carry the TP sharding)")
    p.add_argument("--microbatches", type=int, default=2,
                   help="microbatches per step under --pp")
    p.add_argument("--schedule",
                   choices=("gpipe", "1f1b", "interleaved",
                            "interleaved_1f1b"),
                   default="gpipe", help="pipeline schedule under --pp")
    p.add_argument("--virtual-stages", type=int, default=2,
                   help="model chunks per pp device under the "
                        "interleaved schedules (bubble shrinks V x)")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", type=str, default=None,
                   help="jax.checkpoint_policies name for selective "
                        "remat (e.g. dots_with_no_batch_dims_saveable)")
    p.add_argument("--profile", type=str, default=None, metavar="LOGDIR",
                   help="capture a JAX profiler trace of epoch 0 into "
                        "LOGDIR (view with tensorboard/xprof)")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient accumulation chunks per optimizer "
                        "update (the big-batch update in 1/N the "
                        "activation memory)")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, decode N tokens from the first "
                        "training window's prefix (KV-cached; greedy "
                        "unless --temperature)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature for --generate (0=greedy)")
    p.add_argument("--top-k", type=int, default=None,
                   help="restrict sampling to the k most likely tokens")
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: smallest token set with "
                        "cumulative probability >= p")
    p.add_argument("--experts", type=int, default=0,
                   help="swap the MLP for an expert-parallel MoE with "
                        "this many experts (sharded over any `ep` "
                        "capacity left after dp*pp*tp)")
    p.add_argument("--moe-top-k", type=int, default=1,
                   help="experts per token (1=Switch, 2=GShard)")
    p.add_argument("--config", type=str, default=None, metavar="JSON",
                   help="build the model from a description of its "
                        "architecture instead of --vocab/--dim/--layers/"
                        "--experts: the keys of a published config.json "
                        "(latent attention, shared + routed experts, MTP: "
                        "benchmarks/configs/glm47-flash-ep8.json; gated "
                        "short convolutions among grouped-query attention, "
                        "bias-routed experts, a tied head: "
                        "benchmarks/configs/lfm2-8b-a1b-ep4.json; one-"
                        "branch layers of Mamba-2, rotary-free attention "
                        "and ungated relu² experts: "
                        "benchmarks/configs/nemotron3-nano-ep16.json; "
                        "block-diffusion training over softmax-routed "
                        "experts: benchmarks/configs/sdar-30b-a3b-ep8.json; "
                        "window and full attention over early-routed ReGLU "
                        "experts: "
                        "benchmarks/configs/smallthinker-21b-a3b-ep4.json; "
                        "with --dry-sizes their toy sizes)")
    p.add_argument("--dry-sizes", action="store_true",
                   help="with --config: overlay the file's dry_run block "
                        "(toy widths for the CPU)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--steps", type=int, default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddstore_tpu import DDStore, auto_group
    from ddstore_tpu.data import (DeviceLoader, DistributedSampler,
                                  ShardedDataset)
    from ddstore_tpu.models import transformer
    from ddstore_tpu.parallel import make_mesh
    from ddstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    n_dev = len(jax.local_devices())
    dp = min(args.dp, n_dev)
    pp, tp = args.pp, args.tp
    if n_dev < dp * pp * tp:
        raise SystemExit(f"dp*pp*tp={dp * pp * tp} needs more than the "
                         f"{n_dev} local devices")
    # Largest usable subset (a 6-device host with --dp 4 still trains on
    # 4 devices, matching the pre-pp behavior); leftover capacity after
    # dp*pp*tp becomes the sequence axis.
    sp = n_dev // (dp * pp * tp)
    if args.config:
        sp = 1  # a described architecture is not wired to ring attention
    axes = {"dp": dp}
    if pp > 1:
        axes["pp"] = pp
    if tp > 1:
        axes["tp"] = tp
    if args.experts and sp > 1:
        # Leftover capacity serves experts instead of sequence when an
        # MoE is requested (ep and sp compete for the same devices at
        # this example's scale; real configs pick explicitly).
        axes["ep"] = sp
        sp = 1
    elif sp > 1:
        axes["sp"] = sp
    n_used = 1
    for v in axes.values():
        n_used *= v
    mesh = make_mesh(axes, jax.local_devices()[:n_used])

    # One description builds any architecture: the dense block (or its
    # capacity-bound MoeMlp) from the flags, or a published expert model
    # (latent attention; short convolutions among grouped-query attention;
    # one-branch layers of Mamba-2, attention and ungated experts; a
    # block-diffusion model, whose objective is its description's too)
    # from its config's keys.
    desc = dict(vocab=args.vocab, dim=args.dim, heads=args.dim // 32,
                layers=args.layers, experts=args.experts,
                moe_top_k=args.moe_top_k)
    if args.config:
        import json
        with open(args.config) as f:
            desc = json.load(f)
        if args.dry_sizes:
            desc.update(desc.get("dry_run", {}))
        args.vocab = int(desc["vocab_size"])

    group = auto_group()
    store = DDStore(group)
    rng = np.random.default_rng(args.seed + store.rank)
    # Repeated-pattern corpus (learnable quickly; swap in real token ids).
    base = rng.integers(0, args.vocab, size=64)
    corpus = np.tile(base, args.windows * args.seq // 64 + 2)
    starts = rng.integers(0, len(corpus) - args.seq - 1,
                          size=args.windows)
    windows = np.stack([corpus[s:s + args.seq] for s in starts]
                       ).astype(np.int32)
    nexts = np.stack([corpus[s + 1:s + args.seq + 1] for s in starts]
                     ).astype(np.int32)
    ds = ShardedDataset(store, windows, nexts)

    # XLA's CPU backend crashes promoting bf16 all-reduces that carry a
    # copy (hit by pp/tp compositions); TPU has native bf16 collectives.
    # Smoke runs on virtual CPU devices therefore compute in f32.
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" \
        else jnp.float32
    remat = {}
    if args.remat or args.remat_policy is not None:
        remat = dict(remat=True, remat_policy=args.remat_policy)
    model = transformer.lm_from_description(desc, compute_dtype=dtype,
                                            mesh=mesh, **remat)
    if pp > 1:
        # Pipelined step: stages over pp (megatron-sharded over tp when
        # set, ring attention over sp inside each stage).
        if args.accum_steps != 1:
            raise SystemExit("--accum-steps composes with the sequential "
                             "step only; under --pp use --microbatches")
        nv = args.virtual_stages \
            if args.schedule.startswith("interleaved") else 1
        state, tx = transformer.create_pp_train_state(
            jax.random.key(args.seed), model, n_stages=pp, lr=args.lr,
            mesh=mesh, n_virtual=nv)
        step = transformer.make_pp_train_step(
            model, tx, mesh, n_stages=pp,
            n_microbatches=args.microbatches, schedule=args.schedule,
            n_virtual=nv)
        batch = args.microbatches * 2 * dp
    else:
        lr = args.lr
        if desc.get("lr_warmup_steps"):
            # a description may say how its rate warms up (an expert
            # model's router does not survive the full rate at step 0)
            import optax
            lr = optax.linear_schedule(0.0, float(desc.get("lr", lr)),
                                       int(desc["lr_warmup_steps"]))
        state, tx = transformer.create_train_state(
            jax.random.key(args.seed), model, lr=lr, mesh=mesh)
        step = transformer.make_train_step(model, tx, mesh=mesh,
                                           state=state,
                                           accum_steps=args.accum_steps)
        batch = 2 * dp
        if model.arch is not None and model.arch.router_scoring == "sigmoid":
            # A seeded router is far from the balance a model in training
            # keeps: bring the correction biases there on the first windows
            # (a softmax router has no such bias and no such rule).
            n = min(4, len(windows) // batch)
            cut = lambda a: jnp.asarray(a[:n * batch]).reshape(
                n, batch, args.seq)
            state = transformer.balance_router_bias(
                model, state, cut(windows), cut(nexts),
                jnp.tile(jnp.arange(args.seq, dtype=jnp.int32), (batch, 1)))
        elif model.arch is not None:
            # No bias to move: the deployment chooses which experts this
            # chip holds, by their load on the first windows.
            n = min(transformer.PLACEMENT_BATCHES, len(windows) // batch)
            state = transformer.place_experts(
                model, state, jnp.asarray(windows[:n * batch]).reshape(
                    n, batch, args.seq),
                jnp.tile(jnp.arange(args.seq, dtype=jnp.int32), (batch, 1)))

    sampler = DistributedSampler(len(ds), store.world_group.size,
                                 store.world_group.rank, seed=args.seed)
    pos = jnp.tile(jnp.arange(args.seq, dtype=jnp.int32), (batch, 1))
    import contextlib

    from ddstore_tpu.utils import profile, step_annotate, trace
    for epoch in range(args.epochs):
        sampler.set_epoch(epoch)
        loader = DeviceLoader(ds, sampler, batch_size=batch, mesh=mesh,
                              spec=jax.P("dp", "sp" if sp > 1 else None))
        tracing = trace(args.profile) if (args.profile and epoch == 0) \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        tot, nb, loads = 0.0, 0, []
        with tracing:
            for i, (tok, tgt) in enumerate(loader):
                if args.steps is not None and i >= args.steps:
                    break
                with step_annotate(i):
                    state, loss = step(state, tok, tgt, pos)
                if model.arch is not None:
                    # beside the loss: tokens routed to each expert, a layer
                    loss, load = loss
                    loads.append(load)
                tot += float(loss)
                nb += 1
            # Flush the final async step before stop_trace / timing
            # (state is always defined, even on zero-step runs).
            jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        m = loader.metrics.summary()
        if store.rank == 0:
            tps = nb * batch * args.seq / dt
            print(f"epoch {epoch}: loss={tot / max(1, nb):.4f} "
                  f"tokens/s={tps:.0f} "
                  f"loader_wait_share={m['loader_wait_share']:.4f}"
                  + (expert_rows(np.stack(loads)) if loads else ""),
                  flush=True)
            if epoch == 0:
                # Counted while the step was traced: what each described
                # layer mixes with, and the pairs each sp position's flash
                # calls compute.
                for layer, mix in profile.counters()["mixer_layout"].items():
                    print(f"layer {layer}: " + " ".join(
                        f"{k}={v}" for k, v in mix.items()), flush=True)
                for name, d in profile.counters()["diffusion"].items():
                    print(f"block diffusion {name or 'lm'}: " + " ".join(
                        f"{k}={v}" for k, v in d.items()), flush=True)
                for call, geo in profile.counters()["ring_geometry"].items():
                    print(f"mesh {dict(mesh.shape)} ring {call}: "
                          f"{geo['order']} order, {geo['chunk_rows']} rows "
                          f"a position, pairs computed "
                          f"{geo['pairs_computed']} of needed "
                          f"{geo['pairs_needed']}, largest over mean "
                          f"{geo['max_over_mean']:.2f}", flush=True)
                # Which body each forward call lowered (one_pass: its
                # sub-tile and tau), that the backward held each head's dq
                # in VMEM (its bytes, the limit the call set), and what a
                # sliding window's kernels step over: 1.00 visited over
                # live is a grid of no block wholly outside the window (the
                # kernels run on the chip; the CPU takes the reference)
                for kernel, calls in profile.counters()[
                        "flash_geometry"].items():
                    for call, geo in calls.items():
                        if "body" in geo:
                            print(f"flash {call} {kernel}: body "
                                  f"{geo['body']}" + "".join(
                                      f" {k} {geo[k]}" for k in ("tile", "tau")
                                      if k in geo), flush=True)
                        if "dq" in geo:
                            print(f"flash {call} {kernel}: dq {geo['dq']} "
                                  f"dq_vmem_bytes {geo['dq_vmem_bytes']} "
                                  f"vmem_limit {geo['vmem_limit']}",
                                  flush=True)
                        if call.startswith("window"):
                            seen = geo["grid_steps"] / geo["blocks_live"]
                            print(f"flash {call} {kernel}: pairs needed "
                                  f"{geo['pairs_needed']} computed "
                                  f"{geo['pairs_computed']} visited/live "
                                  f"{seen:.2f}", flush=True)
                # Which blocks compute their forward twice, what the
                # devices hold (where a runtime counts it), and what was
                # compiled against what the cache had.
                counted = profile.counters()
                for block, kept in counted["remat"].items():
                    print(f"block {block}: " + " ".join(
                        f"{k}={v}" for k, v in kept.items()), flush=True)
                for dev, mem in counted.get("memory", {}).items():
                    print(f"memory {dev}: " + " ".join(
                        f"{k}={v}" for k, v in mem.items()), flush=True)
                print("compile cache: " + " ".join(
                    f"{k}={v}" for k, v in counted["compile_cache"].items()),
                    flush=True)
    if args.generate > 0 and store.rank == 0:
        # KV-cached greedy continuation of the first window's prefix —
        # on a learned repeated-pattern corpus the continuation should
        # echo the pattern.
        from ddstore_tpu.models import decode
        infer = model.clone(mesh=None)  # decode is single-host
        params = state.params
        if pp > 1:  # reassemble the stage stacks into flat params
            outer, stages = params
            params = transformer.lm_from_stages(
                jax.device_get(outer), jax.device_get(stages),
                model.layers, pp, n_virtual=nv)
        plen = min(32, args.seq)
        prompt = jnp.asarray(windows[:1, :plen])
        out = decode.generate(infer, params, prompt, args.generate,
                              temperature=args.temperature,
                              key=jax.random.key(args.seed + 1),
                              top_k=args.top_k, top_p=args.top_p)
        cont = np.asarray(out[0, plen:])
        want = corpus[int(starts[0]) + plen:
                      int(starts[0]) + plen + args.generate]
        n = min(len(cont), len(want))  # corpus may end mid-continuation
        acc = float((cont[:n] == want[:n]).mean()) if n else float("nan")
        print(f"generate: {args.generate} tokens, pattern accuracy "
              f"{acc:.2f}: {cont[:24].tolist()}", flush=True)
    store.close()


if __name__ == "__main__":
    main()
