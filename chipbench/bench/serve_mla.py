"""Serve cells of an MLA + MoE model (DeepSeek-V2): back-to-back
``ServeEngine.generate_many`` calls of greedy requests on one chip, which
holds the configuration's share of an expert-parallel deployment.

The run is ``bench/serve.py``'s: weights on the device from the seed, one
warm-up call with a request in every prefill bucket the mix uses, calls
until ``seconds`` have passed, the profiler over the window's first
``trace_seconds``.  The program's configuration is built from the published
``config.json`` keys of the configuration file, with the routed experts
held here as ``experts_held``.  Once the window has closed and the
program's state is freed, the sampled requests go through the plain float32
reference of ``bench/refmodel_mla.py``: the widest gap by which a served
token's logit lies below the reference's best decides ``correct``.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback

import numpy as np

from bench import refmodel_mla, traffic
from bench.harness import CompileCounter, Profiler, memory_peak
from bench.record import Record, Traced
from bench.serve import sample, warm_requests

#: published settings the program implements, as config.json gives them
_FIXED = {"hidden_act": "silu", "attention_bias": False, "q_lora_rank": None,
          "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
          "topk_group": 1, "routed_scaling_factor": 1, "moe_layer_freq": 1,
          "tie_word_embeddings": False}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.models.config import (MLAConfig, MoEConfig, ModelConfig,
                                     YarnConfig)

    for key, want in _FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: the program implements "
                             f"{want!r}")
    y = cfg["rope_scaling"]
    if y["type"] != "yarn":
        raise ValueError(f"rope_scaling type {y['type']!r}")
    held = cfg["experts_held"]
    if held["count"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=False, rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnConfig(
            factor=float(y["factor"]),
            original_max_position_embeddings=int(
                y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        norm_eps=cfg["rms_norm_eps"],
        first_k_dense=cfg["first_k_dense_replace"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(n_experts=cfg["published"]["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      experts_held=(held["first"], held["count"])),
        param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["serve"]["compute_dtype"])


def run(cell, devices, peaks, seed: int, seconds: float,
        trace_dir: str | None, t_start: float, control: bool = False
        ) -> Record:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.api import ServeConfig, ServeEngine
    from repro.models import init_params

    cfg, mix = cell.config, cell.mix
    serve, vocab = cfg["serve"], cfg["vocab_size"]
    if mix["sampling"] != "greedy":
        raise ValueError("the check compares greedy tokens only")
    if cfg["experts_held"]["first"] != 0:
        raise ValueError("the reference holds experts 0 .. count - 1")
    rec = Record("serve", cfg, mix, cell.chips, peaks)
    mcfg = program_config(cfg)
    mesh = Mesh(np.asarray(devices[:1]).reshape(1, 1), ("data", "model"))
    weights = refmodel_mla.make_weights(
        cfg, seed, NamedSharding(mesh, PartitionSpec()))
    # the engine takes the program's parameter layout: check it is ours
    want = jax.eval_shape(lambda k: init_params(k, mcfg), jax.random.key(0))
    if jax.tree.structure(want) != jax.tree.structure(weights) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(weights))):
        raise RuntimeError("the engine's parameter layout differs from the "
                           "benchmark's weights")
    engine = ServeEngine(mcfg, weights, mesh, ServeConfig(
        batch=serve["batch"], max_len=serve["max_len"],
        prefill_bucket=serve["prefill_bucket"], temperature=0.0, seed=0))
    compiles = CompileCounter()
    engine.generate_many(warm_requests(mix, serve, vocab))

    prof = Profiler(trace_dir)
    traced = Traced() if trace_dir else None
    trace_s = float(mix.get("trace_seconds", seconds))
    served = []                         # (prompt, tokens)
    c0 = compiles.count
    rec.setup_s = time.perf_counter() - t_start

    t_win = time.perf_counter()
    t_end = t_win
    call = 0
    prof.start()
    timer = threading.Timer(trace_s, prof.stop)
    if prof.active:
        timer.start()
    while time.perf_counter() - t_win < seconds:
        reqs = traffic.call_requests(mix, seed, call, vocab)
        rec.attempted += len(reqs)
        try:
            with prof.span("generate_many"):
                outs = engine.generate_many(reqs)
        except Exception:
            rec.failed += len(reqs)
            traceback.print_exc()
            break
        t_end = time.perf_counter()
        for (p, m), o in zip(reqs, outs):
            if len(o) != m:
                rec.failed += 1
            rec.tokens += len(o)
            rec.requests.append((p.size, len(o)))
            served.append((p, np.asarray(o)))
        call += 1
    timer.cancel()
    prof.stop()
    if timer.is_alive():
        timer.join()
    rec.window_s = t_end - t_win
    rec.notes["calls"] = call
    rec.compiles_in_window = compiles.count - c0
    rec.memory_peak_bytes = memory_peak(devices)
    del engine, weights
    gc.collect()

    if traced is not None:
        from bench.trace import Trace, find_xplane
        traced.trace = Trace(find_xplane(trace_dir))
        rec.traced = traced

    gap, ctrl = check(cfg, seed, served, mix, control)
    rec.notes["token_gap"] = gap
    if control:
        rec.notes["control_gap"] = ctrl
    rec.checks["token_gap"] = (ctrl if control else gap,
                               cfg["limits"]["token_gap"])
    print(f"serve_mla: {call} calls, {rec.tokens} tokens in "
          f"{rec.window_s:.3f} s, {rec.compiles_in_window} compiles in the "
          f"window, peak {rec.memory_peak_bytes} B; widest gap {gap!r}"
          + (f", control {ctrl!r}" if control else ""), file=sys.stderr)
    return rec


def check(cfg: dict, seed: int, served: list, mix: dict, control: bool
          ) -> tuple[float, float | None]:
    """-> (widest gap of a served token below the reference's best logit,
    the same for the control's choices, when asked)."""
    if not served:
        return float("inf"), None
    w = refmodel_mla.make_weights(cfg, seed)
    m = refmodel_mla.shape(cfg)
    S = cfg["serve"]["max_len"]
    rows = int(mix["output"]["max"])
    widest, widest_ctrl = 0.0, 0.0
    for r in sample(served, seed, int(mix.get("check_requests", 8))):
        prompt, out = served[r]
        n, k = prompt.size, len(out)
        if k == 0:
            continue
        seq = np.zeros(S, np.int32)
        seq[:n], seq[n:n + k - 1] = prompt, out[:-1]
        start = min(n - 1, S - rows)
        at = np.arange(k) + (n - 1 - start)
        ref = np.asarray(refmodel_mla.logits(w, seq, start, m=m,
                                             rows=rows))[at]
        if not np.all(np.isfinite(ref)):
            return float("inf"), None
        best = ref.max(axis=-1)
        widest = max(widest, float(np.max(best - ref[np.arange(k), out])))
        if control:
            low = np.asarray(refmodel_mla.logits(w, seq, start, m=m,
                                                 rows=rows, fp8=True))[at]
            pick = low.argmax(axis=-1)
            widest_ctrl = max(widest_ctrl,
                              float(np.max(best - ref[np.arange(k), pick])))
    return widest, (widest_ctrl if control else None)

