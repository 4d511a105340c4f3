"""Smoke run of the model code on one TPU chip.

    python chip_smoke.py

It runs in this one process and starts no other (a chip belongs to one
process at a time): mamba2-780m at its published widths with the Pallas
SSD kernel (``kernel_mode="pallas"``).  One SSD layer in f32 and the bf16
prefill logits are compared with ``kernel_mode="ref"`` on the same chip,
both sides at full f32 matmul precision; the continuous-batching
``ServingEngine`` answers 8 requests and the ``Trainer`` takes 5 steps.
Weights are random, made from a seed.

Any failed check exits non-zero.  Where JAX finds no TPU the script exits
non-zero before it starts and prints no result.  The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GiB = 1 << 30

# One SSD layer in f32, Pallas kernel vs the jnp reference, both at full
# f32 matmul precision: normwise relative error of y and the final state.
SSD_RTOL = 1e-3
# Prefill logits of the bf16 model, Pallas vs reference path, both at full
# f32 matmul precision: normwise relative error, a few bf16 ulps.  At the
# default precision the comparison cannot tell a fault from rounding: the
# random-weight 48-layer model amplifies the MXU's bf16 passes until the
# reference path differs from itself at full precision by 0.17.
LOGITS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def rel_err(a, ref) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


# ----------------------------------------------------------------- model
def ssd_layer_check(cfg, seq: int, seed: int = 0) -> float:
    """One SSD layer at ``cfg``'s widths in f32, Pallas vs reference, both
    at full f32 matmul precision.  Returns the larger normwise relative
    error of ``y`` and the final state."""
    import jax
    import jax.numpy as jnp

    from repro.models.ssm import ssd_chunked

    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    xh = jax.random.normal(ks[0], (1, seq, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, seq, h)) - 2.0)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h))
    bm = jax.random.normal(ks[2], (1, seq, n)) * n ** -0.5
    cm = jax.random.normal(ks[3], (1, seq, n)) * n ** -0.5
    layer = jax.jit(ssd_chunked, static_argnames=("chunk", "kernel_mode"))
    with jax.default_matmul_precision("highest"):
        y, hf = layer(xh, dt, a_log, bm, cm, chunk=cfg.ssm_chunk,
                      kernel_mode=cfg.kernel_mode)
        y_ref, hf_ref = layer(xh, dt, a_log, bm, cm, chunk=cfg.ssm_chunk,
                              kernel_mode="ref")
    return max(rel_err(y, y_ref), rel_err(hf, hf_ref))


def model_phase(cfg, *, requests: int = 8, prompt_len: int = 512,
                new_tokens: int = 32, slots: int = 4, train_batch: int = 4,
                train_seq: int = 2048, train_steps: int = 5,
                seed: int = 0) -> dict:
    """Serve and train ``cfg`` through ``ServingEngine`` and ``Trainer``,
    checking the SSD layer and the prefill logits against
    ``kernel_mode="ref"``.  Returns the measured errors, the train losses
    and the train step's compile time and memory analysis."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Model
    from repro.runtime import ServingEngine, TrainConfig, Trainer

    out: dict = {}
    ssd_err = ssd_layer_check(cfg, train_seq, seed)
    log(f"ssd layer (f32, {cfg.ssm_heads} heads x {cfg.ssm_head_dim}, "
        f"state {cfg.ssm_state}, chunk {cfg.ssm_chunk}, seq {train_seq}): "
        f"rel err {ssd_err:.3e} <= {SSD_RTOL:g}")
    check(ssd_err <= SSD_RTOL, "SSD layer differs from the reference")
    out["ssd_err"] = ssd_err

    # ---- serve
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, prompt_len, dtype=np.int32)
               for _ in range(requests)]
    engine = ServingEngine(cfg, params, slots=slots,
                           max_len=prompt_len + new_tokens)
    ids = [engine.submit(p, max_new=new_tokens) for p in prompts]
    done = {c.id: c.tokens for c in engine.run_until_drained()}
    check(sorted(done) == sorted(ids), "server did not answer every "
                                       "request")
    check(all(len(t) == new_tokens and all(0 <= x < cfg.vocab for x in t)
              for t in done.values()), "a completion has the wrong length "
                                       "or an out-of-vocabulary token")
    log(f"serve: {requests} requests x {prompt_len} prompt tokens -> "
        f"{new_tokens} new tokens each on {slots} slots; first request "
        f"starts {done[ids[0]][:8]}")

    batch = {"tokens": jnp.asarray(prompts[0][None])}
    logits, _ = jax.jit(model.prefill)(params, batch)
    logits = np.asarray(logits, np.float32)
    check(bool(np.isfinite(logits).all()), "prefill logits are not finite")
    check(done[ids[0]][0] == int(logits[0].argmax()),
          "the server's first token is not the prefill argmax")
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.prefill)(params, batch)
        logits_ref, _ = jax.jit(
            Model(cfg.replace(kernel_mode="ref")).prefill)(params, batch)
    logits_err = rel_err(logits, logits_ref)
    log(f"prefill logits ({cfg.compute_dtype}, vocab {cfg.vocab}, full "
        f"matmul precision): rel err {logits_err:.3e} <= {LOGITS_RTOL:g}")
    check(logits_err <= LOGITS_RTOL, "prefill logits differ from the "
                                     "reference path")
    out["logits_err"] = logits_err
    del engine, params, logits, logits_ref

    # ---- train
    trainer = Trainer(cfg, TrainConfig(batch=train_batch, seq_len=train_seq,
                                       steps=train_steps, log_every=0,
                                       seed=seed))
    tokens = jax.ShapeDtypeStruct((train_batch, train_seq), jnp.int32)
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(
        jax.eval_shape(trainer.init_state),
        {"tokens": tokens, "labels": tokens}).compile()
    out["train_compile_s"] = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    if mem is not None:
        out["train_memory"] = {
            k: getattr(mem, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes")}
    log(f"train step compile (set-up): {out['train_compile_s']:.1f} s; "
        f"memory analysis: {out.get('train_memory')}")
    state, losses = trainer.run()
    del state
    check(len(losses) == train_steps, "trainer took the wrong number of "
                                      "steps")
    check(all(np.isfinite(losses)), f"non-finite train loss: {losses}")
    log(f"train: {train_steps} steps at batch {train_batch} x seq "
        f"{train_seq}, losses {losses}")
    out["losses"] = losses
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}). No result.",
              file=sys.stderr)
        return 1
    from repro.configs.mamba2_780m import CONFIG
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache: {enable_compile_cache()}")
    model_phase(CONFIG.replace(kernel_mode="pallas"))
    peak = dev.memory_stats()["peak_bytes_in_use"]
    log(f"device memory peak: {peak} bytes ({peak / GiB:.2f} GiB)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
