"""Train an MLP with the WHOLE train step compiled as one stitched plan.

``make_stitched_train_step`` captures ``jax.value_and_grad`` of the loss
plus the AdamW update (clipping, cosine LR schedule, per-leaf elementwise
update towers) through ``repro.stitch`` — forward, backward and optimizer
fuse into one kernel plan with donated param/state buffers, bit-identical
to the ``jax.jit`` trainer.

    PYTHONPATH=src python examples/train_stitched.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro import StitchOptions
from repro.train import AdamWConfig, adamw_init, make_stitched_train_step
from repro.train.optimizer import adamw_update

BATCH, D_IN, D_H, D_OUT = 64, 16, 32, 8


def init_params(rng):
    return {
        "w1": jnp.asarray(rng.normal(size=(D_IN, D_H), scale=0.1), jnp.float32),
        "b1": jnp.zeros((D_H,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(D_H, D_OUT), scale=0.1), jnp.float32),
        "b2": jnp.zeros((D_OUT,), jnp.float32),
    }


def loss_fn(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - y) ** 2)


def make_batch(rng):
    return (
        jnp.asarray(rng.normal(size=(BATCH, D_IN)), jnp.float32),
        jnp.asarray(rng.normal(size=(BATCH, D_OUT)), jnp.float32),
    )


def main():
    rng = np.random.default_rng(0)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)

    step = make_stitched_train_step(
        loss_fn, opt_cfg, options=StitchOptions(max_blocks=32)
    )

    # reference trainer on its own copies (the stitched step donates buffers)
    def ref_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state, om = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **om}

    ref = jax.jit(ref_step)

    params = init_params(rng)
    p_st = jax.tree.map(jnp.copy, params)
    p_rf = jax.tree.map(jnp.copy, params)
    s_st, s_rf = adamw_init(p_st), adamw_init(p_rf)

    print("step  stitched-loss  jit-loss       lr        bit-identical")
    for i in range(20):
        batch = make_batch(rng)
        p_st, s_st, m_st = step(p_st, s_st, batch)
        p_rf, s_rf, m_rf = ref(p_rf, s_rf, batch)
        same = np.array_equal(np.asarray(m_st["loss"]), np.asarray(m_rf["loss"]))
        if i % 5 == 0 or i == 19:
            print(f"{i:4d}  {float(m_st['loss']):.6f}      "
                  f"{float(m_rf['loss']):.6f}  {float(m_st['lr']):.2e}  {same}")
        assert same, f"loss diverged from jax.jit at step {i}"

    print()
    print(step.report())
    s = step.stats
    assert step.num_fallbacks == 0
    print(f"\nwhole train step = ONE plan: {s.stitched_kernels} stitched kernels "
          f"vs {s.xla_baseline_kernels} XLA-baseline kernels, 0 fallbacks")


if __name__ == "__main__":
    main()
