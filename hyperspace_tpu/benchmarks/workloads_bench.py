"""On-chip throughput for workloads 3–5 (VERDICT r3 weak #7 / next #8).

BASELINE.json's recorded metrics cover HGCN (workload 2) and the
Poincaré embeddings (workload 1); "COMPLETE" still wants a measured
number per workload, so this module times a standard-config train step
for HyboNet (3), HVAE (4) and product-space embeddings (5) on the live
backend, plus a ≥4k-token HyboNet fwd+bwd leg that exercises the N7
flash kernel in BOTH directions at long context (the r04 flash-backward
criterion).  Rides in bench.py's auto detail as one line per workload.
"""

from __future__ import annotations


def run_workloads_bench(repeats: int = 4, steps: int = 10) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperspace_tpu.benchmarks.hgcn_bench import (
        roofline_fields,
        spread,
        step_cost,
        time_steps_all,
    )
    from hyperspace_tpu.data.mnist import synthetic_mnist
    from hyperspace_tpu.data.text import synthetic_text
    from hyperspace_tpu.data.wordnet import synthetic_tree
    from hyperspace_tpu.models import hvae, hybonet, product_embed as pe

    # default repeats=4: these legs are cheap (ms-scale steps) but the
    # r04 artifact showed ~50% session-to-session drift vs the docs
    # table — min over more repeats + the recorded spread make
    # contention visible (VERDICT r4 weak #8).  An explicit smaller
    # value is honored (quick smoke passes).
    out: dict = {"backend": jax.default_backend()}

    def timed_leg(stepper, state, n_steps):
        """(step_s, roofline dict, state): min-of-repeats + spread +
        the compiled bytes/flops bounds (VERDICT r4 #6)."""
        times, state, _ = time_steps_all(stepper, state, n_steps, repeats)
        step_s = min(times) / n_steps
        roof = roofline_fields(step_cost(stepper, state), step_s)
        return step_s, {"repeat_spread": spread(times), **roof}, state

    def scanned_leg(stepper, state, k=32):
        """Per-step ms of ONE dispatch running k chained steps — the
        reading for legs whose step is shorter than a dispatch.  Runs
        the SAME chunked stepper production training
        uses (train/loop.make_chunked_stepper, the CLI ``scan_chunk``
        path), so the ``scan_chunk_*`` fields measure the shipped code,
        not a bench-only twin."""
        from hyperspace_tpu.train.loop import make_chunked_stepper

        run = make_chunked_stepper(stepper, k)
        times, _, _ = time_steps_all(run, state, 1, repeats)
        return round(min(times) / k * 1e3, 3)

    def scan_fields(step_s, scan_ms, k=32):
        """The chunked-dispatch win, quantified per leg: K, per-step ms
        at K, and the per-step dispatch overhead the chunking removed
        (stepwise ms − scanned ms)."""
        return {
            "scan_chunk_k": k,
            "scan_chunk_step_ms": scan_ms,
            "scan_chunk_dispatch_overhead_ms": round(
                step_s * 1e3 - scan_ms, 3),
        }

    # --- HyboNet (workload 3): transformer classifier, flash attention
    cfg = hybonet.HyboNetConfig(vocab_size=8192, num_classes=8, max_len=128,
                                dim=128, num_heads=4, num_layers=2,
                                batch_size=256)
    ds = synthetic_text(num_samples=2048, vocab_size=cfg.vocab_size,
                        num_classes=cfg.num_classes, max_len=cfg.max_len,
                        min_len=cfg.max_len // 2, seed=0)
    model, opt, state = hybonet.init_model(cfg, seed=0)
    toks = jnp.asarray(ds.tokens)
    mask = jnp.asarray(ds.mask)
    labels = jnp.asarray(ds.labels)
    step_s, roof, state = timed_leg(
        lambda st: hybonet.train_step_sampled(model, opt, st, toks, mask,
                                              labels),
        state, steps)
    out["hybonet"] = {
        "step_ms": round(step_s * 1e3, 3),
        "tokens_per_s": round(cfg.batch_size * cfg.max_len / step_s, 1),
        "batch": [cfg.batch_size, cfg.max_len],
        "dim": cfg.dim, "layers": cfg.num_layers,
        "attention_impl": cfg.attention_impl,
        "precision": cfg.precision,
        **roof,
    }

    # --- HyboNet long context: 4k tokens fwd+bwd through the flash
    # kernel (forward online-softmax, recomputing backward — no [L, L]
    # score matrix in either direction)
    lcfg = hybonet.HyboNetConfig(vocab_size=8192, num_classes=8,
                                 max_len=4096, dim=64, num_heads=2,
                                 num_layers=1, batch_size=2)
    lds = synthetic_text(num_samples=4, vocab_size=lcfg.vocab_size,
                         num_classes=lcfg.num_classes, max_len=lcfg.max_len,
                         min_len=lcfg.max_len - 1, seed=0)
    lmodel, lopt, lstate = hybonet.init_model(lcfg, seed=0)
    lt, lm, ll = (jnp.asarray(lds.tokens[: lcfg.batch_size]),
                  jnp.asarray(lds.mask[: lcfg.batch_size]),
                  jnp.asarray(lds.labels[: lcfg.batch_size]))
    step_s, roof, lstate = timed_leg(
        lambda st: hybonet.train_step(lmodel, lopt, st, lt, lm, ll),
        lstate, max(steps // 2, 3))
    out["hybonet_long"] = {
        "step_ms": round(step_s * 1e3, 3),
        "tokens_per_s": round(lcfg.batch_size * lcfg.max_len / step_s, 1),
        "batch": [lcfg.batch_size, lcfg.max_len],
        "fwd_bwd": "flash both directions",
        **roof,
    }

    # --- HVAE (workload 4)
    hcfg = hvae.HVAEConfig(batch_size=256)
    hds = synthetic_mnist(num_samples=2048, seed=0)
    hmodel, hopt, hstate = hvae.init_model(hcfg, seed=0)
    x_all = jnp.asarray(hds.images, hcfg.dtype)

    def hvae_step(st):
        st, loss, recon, kl = hvae.train_step_sampled(hmodel, hopt, st,
                                                      x_all)
        return st, loss

    step_s, roof, hstate = timed_leg(hvae_step, hstate, steps)
    scan_ms = scanned_leg(hvae_step, hstate)
    out["hvae"] = {
        "step_ms": round(step_s * 1e3, 3),
        "images_per_s": round(hcfg.batch_size / step_s, 1),
        **scan_fields(step_s, scan_ms),
        "scan_chunk_images_per_s": round(
            hcfg.batch_size / (scan_ms / 1e3), 1),
        "batch": [hcfg.batch_size, hcfg.image_size, hcfg.image_size],
        "kind": hcfg.kind,
        "precision": hcfg.precision,
        **roof,
    }

    # --- product-space embeddings (workload 5): WordNet-noun-scale table
    tree = synthetic_tree(depth=5, branching=9)
    pcfg = pe.ProductEmbedConfig(num_nodes=tree.num_nodes, batch_size=1024)
    pstate, curv_opt = pe.init_state(pcfg, seed=0)
    pairs = jnp.asarray(tree.pairs)
    p_step = lambda st: pe.train_step(pcfg, curv_opt, st, pairs)
    step_s, roof, pstate = timed_leg(p_step, pstate, steps)
    scan_ms = scanned_leg(p_step, pstate)
    out["product_embed"] = {
        "step_ms": round(step_s * 1e3, 3),
        "pairs_per_s": round(pcfg.batch_size / step_s, 1),
        **scan_fields(step_s, scan_ms),
        "scan_chunk_pairs_per_s": round(
            pcfg.batch_size / (scan_ms / 1e3), 1),
        "num_nodes": tree.num_nodes,
        "factors": [list(f) for f in pcfg.factors],
        "precision": pcfg.precision,
        **roof,
    }
    return out
