"""Training driver: real training on CPU/TPU at any scale.

Fault-tolerance contract (DESIGN.md §4):
  - checkpoint manager with atomic commits + resume-from-latest
  - SIGTERM/SIGINT → checkpoint-and-exit (preemption-safe)
  - deterministic stateless data pipeline (step -> batch)
  - elastic restore: checkpoints reshard onto whatever mesh is current

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-7b --smoke \
      --steps 200 --batch 8 --seq 128 [--quant moss|bf16|per_tensor|...]
  # published widths, depth cut to fit one chip:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-7b --full \
      --layers 2 --steps 3 --batch 2 --seq 2048
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

import jax

from repro.checkpoint import manager as ckpt
from repro.configs.registry import get_config
from repro.core.formats import QuantConfig
from repro.core.introspect import count_pallas_calls
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.obs.trace import span
from repro.train.steps import TrainHParams, init_train_state, make_train_step

_PREEMPTED = False


def _handle_preempt(signum, frame):
    global _PREEMPTED
    _PREEMPTED = True


def quant_from_name(name: str, interval: int = 500,
                    grad_comm_fp8: bool = False) -> QuantConfig:
    if name == "bf16":
        return QuantConfig(mode="bf16", grad_comm_fp8=grad_comm_fp8)
    scaling = "auto" if name == "moss" else "jit"
    return QuantConfig(mode=name if name != "moss" else "moss",
                       weight_scaling=scaling, rescale_interval=interval,
                       grad_comm_fp8=grad_comm_fp8)


def train(arch: str, *, smoke: bool = True, steps: int = 100,
          batch: int = 8, seq: int = 128, quant: str = "moss",
          lr: float = 3e-4, warmup: int = 20, ckpt_dir: str | None = None,
          ckpt_every: int = 50, seed: int = 0, log_every: int = 10,
          mesh=None, microbatches: int = 1, interval: int = 500,
          grad_comm_fp8: bool = False, layers: int | None = None,
          log=print):
    cfg = get_config(arch, smoke=smoke, layers=layers).replace(
        quant=quant_from_name(quant, interval, grad_comm_fp8))
    hp = TrainHParams(peak_lr=lr, warmup_steps=warmup, total_steps=steps,
                      microbatches=microbatches)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch, seed=seed))

    key = jax.random.PRNGKey(seed)
    if mesh is None:
        state = init_train_state(cfg, hp, key)
    else:
        # born sharded by the logical-axis rules: a replicated full-width
        # state would not fit one device
        from repro.launch.specs import state_shardings

        state = jax.jit(lambda k: init_train_state(cfg, hp, k),
                        out_shardings=state_shardings(cfg, mesh))(key)
    start_step = 0
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        state, start_step = ckpt.restore(ckpt_dir, state)
        log(f"resumed from checkpoint at step {start_step}")

    step_fn = make_train_step(cfg, hp, mesh)
    ctx = use_mesh(mesh) if mesh is not None else _nullcontext()
    signal.signal(signal.SIGTERM, _handle_preempt)

    history = []
    with ctx:
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        t0 = time.time()
        tokens_done = 0
        for step in range(start_step, steps):
            with span("train.data", step=step):
                b = data.batch_for_step(step, mesh)
                if cfg.input_mode == "embeddings":
                    # modality-frontend stub: embed tokens with a fixed
                    # random projection (precomputed frame/patch
                    # embeddings)
                    b = dict(b)
                    b["embeds"] = _stub_embeds(cfg, b["tokens"])
            if step == start_step:
                # compile before the first step (the step call then
                # reuses it): on a TPU the kernel count shows that the
                # GEMMs run the Pallas kernels, not the reference
                compiled = jitted.lower(state, b).compile()
                log(f"train step compiled: {count_pallas_calls(compiled)}"
                    f" Pallas kernel calls")
            t_step = time.time()
            with span("train.step", step=step):
                # the span is the dispatch; the step's device time is in
                # the profiler trace beside it (repro.train.step)
                state, metrics = jitted(state, b)
            tokens_done += batch * seq
            if (step + 1) % log_every == 0 or step + 1 == steps:
                loss = float(metrics["loss"])
                now = time.time()
                tps = tokens_done / (now - t0)
                log(f"step {step+1:5d} loss {loss:.4f} "
                    f"lr {float(metrics['lr']):.2e} "
                    f"gnorm {float(metrics['grad_norm']):.2f} "
                    f"step_s {now - t_step:.3f} tok/s {tps:,.0f}")
                history.append((step + 1, loss))
            if ckpt_dir and ((step + 1) % ckpt_every == 0 or _PREEMPTED
                             or step + 1 == steps):
                ckpt.save(ckpt_dir, step + 1, state)
                if _PREEMPTED:
                    log("preemption signal: checkpointed, exiting")
                    sys.exit(42)
    return state, history


def _stub_embeds(cfg, tokens):
    import jax.numpy as jnp
    key = jax.random.PRNGKey(1234)
    table = jax.random.normal(key, (cfg.vocab, cfg.d_model),
                              jnp.float32) * 0.02
    return jnp.take(table, tokens, axis=0).astype(jnp.bfloat16)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="published widths (default: the smoke config)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--quant", default="moss",
                    choices=["moss", "bf16", "per_tensor", "per_group"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-comm-fp8", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="'host:<model>' to train over all local devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh and args.mesh.startswith("host"):
        model = int(args.mesh.split(":")[1]) if ":" in args.mesh else 1
        mesh = make_host_mesh(model=model)

    train(args.arch, smoke=args.smoke, steps=args.steps,
          batch=args.batch, seq=args.seq, quant=args.quant, lr=args.lr,
          ckpt_dir=args.ckpt_dir, microbatches=args.microbatches,
          grad_comm_fp8=args.grad_comm_fp8, mesh=mesh, seed=args.seed,
          layers=args.layers)


if __name__ == "__main__":
    main()
