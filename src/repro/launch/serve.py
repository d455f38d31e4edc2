"""Serving launchers: the LM continuous-batching loop and the
multi-tenant simulation service (``repro.serve``).

LM mode (default)::

    python -m repro.launch.serve --arch smollm-135m --reduced --requests 16

Continuous-batching-lite: requests arrive with different prompt lengths;
the server prefills them (left-padded into the KV cache), then decodes in
lockstep batches, retiring sequences as they hit EOS/max-new-tokens and
admitting queued requests into freed slots.  Prefill feeds the prompt
through the batched decode step but commits the state update to the
admitting slot ONLY (``merge_slot_state``) — the other slots' caches are
bitwise untouched, so one tenant's prompt can never leak into another's
attention window.

Simulation mode (``--sim``)::

    python -m repro.launch.serve --sim --tenants 8 --lanes 4 --t-end 6

Drives ``repro.serve.SimService`` — continuous admission over a vmapped
FAP round, per-tenant quarantine/retry, QoS classes and overload
shedding — and prints the detected-never-silent ``ServeResult``
accounting.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import lm


def merge_slot_state(new_state, old_state, slot: int, batch: int):
    """Keep slot ``slot``'s updates from ``new_state``; every other slot
    keeps ``old_state`` bitwise.

    Every decode-state family (dense KV, MoE, SSM conv/state, hybrid,
    audio cross-attn) lays its leaves out as [n_layers, B, ...] — batch
    at axis 1 — so a one-hot select over that axis masks the prefill
    write generically, whatever the architecture.
    """
    keep = jnp.zeros((batch,), bool).at[slot].set(True)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(keep.reshape((1, batch) + (1,) * (n.ndim - 2)),
                               n, o),
        new_state, old_state)


def lm_main(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))

    # request queue: (id, prompt tokens)
    queue = [(i, rng.integers(0, cfg.vocab, rng.integers(4, 32)))
             for i in range(args.requests)]
    B, S = args.slots, args.cache_len

    @jax.jit
    def jdecode(params, token, state, t_pos):
        return lm.decode_step(cfg, params, token, state, t_pos)

    state = lm.make_decode_state(cfg, B, S)
    slot_free = [True] * B
    slot_req = [None] * B
    slot_left = [0] * B
    cur_tok = np.zeros((B, 1), np.int32)
    done, n_tokens = 0, 0
    t_pos = 0
    t0 = time.time()
    while done < args.requests or any(not f for f in slot_free):
        # admit
        for b in range(B):
            if slot_free[b] and queue:
                rid, prompt = queue.pop(0)
                # prefill by feeding prompt tokens through decode steps,
                # committing the cache write to slot b only — active
                # neighbours' KV windows stay bitwise untouched
                for tok in prompt[:-1]:
                    if t_pos >= S - args.max_new - 1:
                        break
                    logits, new_state = jdecode(
                        params,
                        jnp.asarray(np.full((B, 1), tok, np.int32)),
                        state, jnp.int32(t_pos))
                    state = merge_slot_state(new_state, state, b, B)
                    t_pos += 1
                cur_tok[b, 0] = prompt[-1]
                slot_free[b] = False
                slot_req[b] = rid
                slot_left[b] = args.max_new
        if all(slot_free):
            break
        # decode one step for the whole batch
        logits, state = jdecode(params, jnp.asarray(cur_tok), state,
                                jnp.int32(t_pos))
        t_pos += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        for b in range(B):
            if slot_free[b]:
                continue
            cur_tok[b, 0] = nxt[b]
            n_tokens += 1
            slot_left[b] -= 1
            if slot_left[b] <= 0 or t_pos >= S - 1:
                slot_free[b] = True
                done += 1
        if t_pos >= S - 2:
            # cache exhausted: reset window (toy rollover)
            state = lm.make_decode_state(cfg, B, S)
            t_pos = 0
    dt = time.time() - t0
    print(f"served {done} requests, {n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens/max(dt,1e-9):.1f} tok/s, slots={B})")
    return 0


def sim_main(args):
    from repro.checkpoint import ExponentialBackoff, FaultPlan
    from repro.core import morphology, network
    from repro.core.cell import CellModel
    from repro.serve import SimService, TenantRequest

    model = CellModel(morphology.soma_only())
    net = network.make_network(args.n, k_in=4, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    fault = None
    if args.poison_tenant >= 0:
        fault = FaultPlan(poison_at_round=args.poison_round,
                          poison_tenant=args.poison_tenant, poison_lane=0)
    svc = SimService(model, net, t_end=args.t_end, lanes=args.lanes,
                     queue_cap=args.queue_cap,
                     backoff=ExponentialBackoff(max_retries=args.max_retries),
                     qos_caps={0: max(2, args.n // 4)}, fault=fault,
                     ckpt_dir=args.ckpt_dir or None,
                     checkpoint_every=args.checkpoint_every)
    for rid in range(args.tenants):
        svc.submit(TenantRequest(
            rid=rid, iinj=float(0.14 + 0.03 * rng.random()),
            qos=int(rng.integers(0, 2))))
    t0 = time.time()
    res = svc.run()
    dt = time.time() - t0
    print(f"served {res.submitted} tenants in {res.rounds} rounds "
          f"({dt:.2f}s): {res.completed} completed, {res.evicted} evicted, "
          f"{res.rejected} rejected ({res.shed} shed), "
          f"{res.retried} retries / {res.quarantines} quarantines")
    w = res.health["admission_wait_rounds"]
    print(f"admission wait: mean {w['mean']:.1f} / max {w['max']} rounds; "
          f"straggler: {res.health['straggler']['flagged']} flagged of "
          f"{res.health['straggler']['recorded']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sim", action="store_true",
                    help="serve FAP simulations (repro.serve) instead of LM")
    # LM mode
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4, help="batch slots")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    # simulation mode
    ap.add_argument("--n", type=int, default=12, help="neurons per tenant")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--t-end", type=float, default=6.0)
    ap.add_argument("--queue-cap", type=int, default=8)
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--poison-tenant", type=int, default=-1,
                    help="rid to poison (FaultPlan demo; -1 = off)")
    ap.add_argument("--poison-round", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    return sim_main(args) if args.sim else lm_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
