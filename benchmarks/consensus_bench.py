"""Consensus benchmarks, both layers of the stack:

* `run` (group "consensus_lm") — beyond-paper: the paper's consensus
  algorithms as training data-parallelism, measured on ACTUAL training.
  Trains the same tiny LM for N steps under allreduce / diffusion / admm
  on an emulated 4-replica mesh (subprocess with host devices) and reports
  final losses + replica disagreement.
* `vb_run` (group "consensus_vb") — the adaptive-penalty dVB-ADMM
  subsystem on the paper's GMM instance: plain Algorithm 2 vs
  `ADMMConsensus(adaptive_rho=True)`, with the `ConsensusDiagnostics`
  summary (dual-activation iteration, final rho, clip/reset totals) in the
  derived column and the --json snapshot.  This is the benchmark-level
  guard on the docs/admm-convergence.md convergence story.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks import common

_CODE = r"""
import jax, json
from repro.configs.base import ModelConfig
from repro.training import train_step as ts
from repro.training.trainer import Trainer

cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2, d_model=128,
                  n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=512,
                  tie_embeddings=True, param_dtype="float32",
                  compute_dtype="float32")
out = {}
for mode in ["allreduce", "diffusion", "admm"]:
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    axis = "data" if mode != "allreduce" else None
    tr = Trainer(cfg, mesh, dp_mode=mode, consensus_axis=axis,
                 hyper=ts.TrainHyper(peak_lr=3e-3, warmup=5, total_steps=60),
                 global_batch=8, seq_len=128, seed=0)
    hist = tr.run(60, log_every=60)
    out[mode] = {"first": hist[0]["loss"], "final": hist[-1]["loss"],
                 "resid": hist[-1].get("consensus_residual")}
print("RESULT" + json.dumps(out))
"""


def vb_run(full=False):
    """Adaptive-penalty dVB-ADMM vs plain Algorithm 2 + diagnostics row."""
    import jax
    import jax.numpy as jnp
    from repro.core import algorithms
    from repro.data import synthetic

    x64_before = jax.config.jax_enable_x64
    try:
        K, D = 3, 2
        n_nodes, n_per, n_iters = (50, 100, 1500) if full else (20, 60, 300)
        data = synthetic.paper_synthetic(n_nodes=n_nodes, n_per_node=n_per,
                                         seed=1)
        s = common.setup_gmm(data, K, D, seed=0, graph_seed=3)  # enables x64
        kw = dict(n_iters=n_iters, K=K, D=D, ref_phi=s["ref_phis"],
                  init_q=s["init_q"])

        cvb = algorithms.run_cvb(data.x, data.mask, s["prior"], **kw)

        def run_adaptive():
            return algorithms.run_dvb_admm(data.x, data.mask, s["adj"],
                                           s["prior"], rho=0.5,
                                           adaptive_rho=True, **kw)

        adaptive = run_adaptive()
        jax.block_until_ready(adaptive.phi)          # warm the whole-run jit
        t0 = time.perf_counter()
        adaptive = run_adaptive()
        jax.block_until_ready(adaptive.phi)
        us = (time.perf_counter() - t0) / n_iters * 1e6
        plain = algorithms.run_dvb_admm(data.x, data.mask, s["adj"],
                                        s["prior"], rho=0.5, **kw)

        d = adaptive.consensus_diag
        dual_on_at = (int(jnp.argmax(d.dual_on))
                      if float(d.dual_on[-1]) else -1)
        summary = dict(
            kl_cvb=float(cvb.kl_mean[-1]),
            kl_adaptive=float(adaptive.kl_mean[-1]),
            kl_plain=float(plain.kl_mean[-1]),
            dual_on_at=dual_on_at,
            rho_final=float(jnp.mean(d.rho[-1])),
            clips=int(jnp.sum(d.clip_count)),
            resets=int(jnp.sum(d.reset_count)),
            primal_resid_final=float(jnp.mean(d.primal_resid[-1])),
            dual_resid_final=float(jnp.mean(d.dual_resid[-1])))
        common.save("consensus_vb_adaptive", summary)
        return [("consensus_vb_adaptive", us,
                 f"kl adaptive={summary['kl_adaptive']:.2f} "
                 f"cvb={summary['kl_cvb']:.2f} "
                 f"plain={summary['kl_plain']:.1e} "
                 f"dual_on@{dual_on_at} rho={summary['rho_final']:.2f} "
                 f"clips={summary['clips']}")]
    finally:
        jax.config.update("jax_enable_x64", x64_before)


def run(full=False):
    # the child emulates four host devices; it must never reach for an
    # accelerator, which this process may already hold
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(here, "src")
    proc = subprocess.run([sys.executable, "-c", _CODE], env=env, cwd=here,
                          capture_output=True, text=True, timeout=1800)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    if not line:
        raise RuntimeError(proc.stdout[-2000:] + proc.stderr[-2000:])
    res = json.loads(line[0][len("RESULT"):])
    common.save("consensus_lm", res)
    ar, df, ad = (res[m]["final"] for m in ("allreduce", "diffusion", "admm"))
    return [("consensus_lm_training", 0.0,
             f"final_loss ar={ar:.3f} diffusion={df:.3f} admm={ad:.3f} "
             f"resid_diff={res['diffusion']['resid']:.1e}")]
