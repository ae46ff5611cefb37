"""PPO post-training example: llama actor + KV-cache rollouts + RLVR.

The framework's RL entry (reference ``atorch/rl``: PPO trainer + model
engine, generation delegated to vllm — here rollouts run through the
in-framework KV-cache decoder, ``rl/engine.py llama_cached_generate``).
The task is verifiable-reward style: the policy earns reward for
emitting a target token, so learning is measurable without a reward
model.

    python examples/rl_ppo.py --iterations 30
"""

from __future__ import annotations

import argparse
import os
import sys

# Runnable directly from a checkout: `python examples/rl_ppo.py` puts
# examples/ (not the repo root) on sys.path.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--iterations", type=int, default=30)
    p.add_argument("--rollout_batch", type=int, default=64)
    p.add_argument("--response_len", type=int, default=4)
    p.add_argument("--prompt_len", type=int, default=2)
    p.add_argument("--target_token", type=int, default=7)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--quant_kv", action="store_true",
                   help="int8 kv cache for rollouts (half the decode "
                        "HBM traffic)")
    p.add_argument("--llama", action="store_true",
                   help="tiny-llama actor with KV-cache rollouts "
                        "(default: a 1-layer toy LM — faster on CPU)")
    return p.parse_args()


def main() -> int:
    args = parse_args()
    if args.iterations <= 0:
        print("--iterations must be positive", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.rl.config import PPOConfig
    from dlrover_tpu.rl.engine import ModelEngine, ModelRole, RoleSpec
    from dlrover_tpu.rl.trainer import PPOTrainer

    cfg = PPOConfig(
        rollout_batch_size=args.rollout_batch,
        minibatch_size=args.rollout_batch // 2,
        response_length=args.response_len,
        ppo_epochs=4,
        actor_lr=args.lr,
        critic_lr=args.lr,
        init_kl_coef=0.02,
        temperature=1.0,
    )
    target = args.target_token

    def reward(tokens: np.ndarray) -> np.ndarray:
        resp = tokens[:, args.prompt_len:]
        return (resp == target).mean(axis=1).astype(np.float32) * 2.0

    rng = jax.random.PRNGKey(0)
    if args.llama:
        from dlrover_tpu.models import llama
        from dlrover_tpu.rl.engine import llama_cached_generate

        mcfg = llama.LlamaConfig.tiny(
            n_layer=2, max_seq_len=args.prompt_len + args.response_len + 8
        )
        actor_params = llama.init_params(rng, mcfg)
        actor = RoleSpec(
            lambda p, t: llama.forward(p, t, mcfg)[0],
            actor_params,
            trainable=True,
            generate_fn=llama_cached_generate(
                mcfg, cfg, quant_kv=args.quant_kv
            ),
        )
        vocab = mcfg.vocab_size
    else:
        vocab = 32
        hidden = 32
        k1, k2, k3 = jax.random.split(rng, 3)
        params = {
            "emb": jax.random.normal(k1, (vocab, hidden)) * 0.1,
            "w": jax.random.normal(k2, (hidden, hidden)) * 0.1,
            "out": jax.random.normal(k3, (hidden, vocab)) * 0.1,
        }

        def lm_apply(p, tokens):
            h = jnp.tanh(p["emb"][tokens] @ p["w"])
            return h @ p["out"]

        actor = RoleSpec(lm_apply, params, trainable=True)

    ck1, ck2 = jax.random.split(jax.random.PRNGKey(1))
    chidden = 32
    critic_params = {
        "emb": jax.random.normal(ck1, (vocab, chidden)) * 0.1,
        "v": jax.random.normal(ck2, (chidden,)) * 0.1,
    }

    def critic_apply(p, tokens):
        return jnp.tanh(p["emb"][tokens]) @ p["v"]

    engine = ModelEngine(
        {
            ModelRole.ACTOR: actor,
            ModelRole.CRITIC: RoleSpec(
                critic_apply, critic_params, trainable=True
            ),
        },
        cfg,
        reward_fn=reward,
    )
    trainer = PPOTrainer(engine, cfg, seed=0)
    prompts = np.ones(
        (cfg.rollout_batch_size, args.prompt_len), np.int32
    )

    def prompt_iter():
        while True:
            yield prompts

    first = trainer.make_experience(prompts)
    trainer.buffer.clear()
    print(f"iteration 0: score={first['score_mean']:.3f}", flush=True)
    stats = trainer.learn(
        prompt_iter(), total_iterations=args.iterations, log_every=5
    )
    toks = np.asarray(
        engine.generate(
            jnp.asarray(prompts), jax.random.PRNGKey(9)
        )
    )
    frac = float((toks[:, args.prompt_len:] == target).mean())
    print(
        f"TRAIN_DONE iterations={args.iterations} "
        f"score={stats['score_mean']:.3f} "
        f"(from {first['score_mean']:.3f}) target_frac={frac:.3f}",
        flush=True,
    )
    return 0 if stats["score_mean"] > first["score_mean"] else 1


if __name__ == "__main__":
    sys.exit(main())
