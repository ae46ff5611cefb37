"""HF Llama checkpoint import: logit parity against transformers'
LlamaForCausalLM on a tiny random model (the checkpoints the reference's
llama2 example fine-tunes must load here directly)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _tiny_hf(tie=False, kv_heads=2):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=kv_heads,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        attention_bias=False,
        mlp_bias=False,
        tie_word_embeddings=tie,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval().float()
    return model


class TestHfConvert:
    @pytest.mark.parametrize("tie,kv", [(False, 2), (True, 4)])
    def test_logit_parity(self, tie, kv):
        from dlrover_tpu.models import hf_convert, llama

        model = _tiny_hf(tie=tie, kv_heads=kv)
        params, cfg = hf_convert.from_hf_llama(model)
        assert cfg.n_kv_head == kv

        rng = np.random.RandomState(0)
        tokens = rng.randint(0, 256, size=(2, 19)).astype(np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
        ours, _ = llama.forward(
            params, jnp.asarray(tokens.astype(np.int32)), cfg,
            attn_impl="reference",
        )
        np.testing.assert_allclose(
            np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-4
        )

    def test_state_dict_needs_cfg(self):
        from dlrover_tpu.models import hf_convert

        model = _tiny_hf()
        with pytest.raises(ValueError, match="cfg"):
            hf_convert.from_hf_llama(model.state_dict())

    def test_converted_model_decodes(self):
        from dlrover_tpu.models import hf_convert, llama_infer

        model = _tiny_hf()
        params, cfg = hf_convert.from_hf_llama(model)
        out = llama_infer.generate(
            params, cfg, jnp.ones((1, 4), jnp.int32), max_new_tokens=4,
            temperature=0.0,
        )
        assert out.shape == (1, 8)


class TestStreamingDirImport:
    """Per-tensor streaming import of a checkpoint DIRECTORY (VERDICT r3
    missing #5: the in-memory converter holds ~4x a 7B checkpoint in
    host RAM; this path holds ~one tensor)."""

    @pytest.mark.parametrize("tie", [False, True])
    def test_dir_matches_in_memory_converter(self, tmp_path, tie):
        from dlrover_tpu.models import hf_convert

        model = _tiny_hf(tie=tie)
        # Tiny shard size forces a sharded model.safetensors.index.json
        # — the layout real 7B checkpoints use.
        model.save_pretrained(str(tmp_path), max_shard_size="100KB")
        assert (tmp_path / "model.safetensors.index.json").exists()

        want, want_cfg = hf_convert.from_hf_llama(model)
        got, got_cfg = hf_convert.from_hf_llama_dir(
            str(tmp_path), dtype=jnp.float32
        )
        assert got_cfg == want_cfg
        wl, gl = (jax.tree_util.tree_leaves(t) for t in (want, got))
        assert len(wl) == len(gl)
        for a, b in zip(wl, gl):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dir_single_file_and_logit_parity(self, tmp_path):
        from dlrover_tpu.models import hf_convert, llama

        model = _tiny_hf()
        model.save_pretrained(str(tmp_path))  # single model.safetensors
        params, cfg = hf_convert.from_hf_llama_dir(
            str(tmp_path), dtype=jnp.float32
        )
        tokens = np.random.RandomState(0).randint(
            0, 256, size=(2, 11)
        ).astype(np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
        ours, _ = llama.forward(
            params, jnp.asarray(tokens.astype(np.int32)), cfg,
            attn_impl="reference",
        )
        np.testing.assert_allclose(
            np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-4
        )

    def test_dir_sharded_placement(self, tmp_path, cpu_mesh_devices):
        """shardings= places every leaf straight onto its target
        NamedSharding — no replicated host-side detour."""
        from jax.sharding import Mesh

        from dlrover_tpu.models import hf_convert, llama
        from dlrover_tpu.parallel.accelerate import infer_param_specs
        from dlrover_tpu.parallel.mesh import MeshSpec
        from dlrover_tpu.parallel.sharding import named_sharding_tree

        model = _tiny_hf()
        model.save_pretrained(str(tmp_path), max_shard_size="100KB")
        cfg = hf_convert.config_from_hf_dir(str(tmp_path))
        shape = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
        )
        spec = MeshSpec(fsdp=4)
        mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("fsdp",))
        shardings = named_sharding_tree(
            infer_param_specs(shape, spec), mesh
        )
        params, _ = hf_convert.from_hf_llama_dir(
            str(tmp_path), dtype=jnp.float32, shardings=shardings
        )
        wq = params["layers"][0]["wq"]
        assert "fsdp" in str(wq.sharding.spec)
        # Values still correct under placement.
        want, _ = hf_convert.from_hf_llama(model)
        np.testing.assert_array_equal(
            np.asarray(wq), np.asarray(want["layers"][0]["wq"])
        )

    def test_dir_peak_rss_bounded(self, tmp_path):
        """Synthetic multi-shard checkpoint: the loader's peak RSS must
        stay well under a full-state-dict materialization (which costs
        >= file_bytes on top of the output tree)."""
        import json
        import subprocess
        import sys

        from safetensors.numpy import save_file

        # ~190MB of f32 across 13 shards, llama-shaped names — big
        # enough that the streaming/naive gap dwarfs allocator noise.
        rng = np.random.RandomState(0)
        D, FF, L, V = 512, 1408, 12, 8192
        index = {"weight_map": {}}

        def shard(fname, tensors):
            save_file(tensors, str(tmp_path / fname))
            for k in tensors:
                index["weight_map"][k] = fname

        shard("s0.safetensors", {
            "model.embed_tokens.weight":
                rng.randn(V, D).astype(np.float32),
            "lm_head.weight": rng.randn(V, D).astype(np.float32),
            "model.norm.weight": np.ones(D, np.float32),
        })
        for i in range(L):
            p = f"model.layers.{i}."
            shard(f"s{i + 1}.safetensors", {
                p + "input_layernorm.weight": np.ones(D, np.float32),
                p + "post_attention_layernorm.weight":
                    np.ones(D, np.float32),
                p + "self_attn.q_proj.weight":
                    rng.randn(D, D).astype(np.float32),
                p + "self_attn.k_proj.weight":
                    rng.randn(D, D).astype(np.float32),
                p + "self_attn.v_proj.weight":
                    rng.randn(D, D).astype(np.float32),
                p + "self_attn.o_proj.weight":
                    rng.randn(D, D).astype(np.float32),
                p + "mlp.gate_proj.weight":
                    rng.randn(FF, D).astype(np.float32),
                p + "mlp.up_proj.weight":
                    rng.randn(FF, D).astype(np.float32),
                p + "mlp.down_proj.weight":
                    rng.randn(D, FF).astype(np.float32),
            })
        with open(tmp_path / "model.safetensors.index.json", "w") as f:
            json.dump(index, f)
        with open(tmp_path / "config.json", "w") as f:
            json.dump({
                "vocab_size": V, "hidden_size": D,
                "intermediate_size": FF, "num_hidden_layers": L,
                "num_attention_heads": 8, "num_key_value_heads": 8,
                "max_position_embeddings": 128,
            }, f)
        file_bytes = sum(
            (tmp_path / f).stat().st_size
            for f in os.listdir(tmp_path) if f.endswith(".safetensors")
        )
        assert file_bytes > 150e6  # the probe is meaningless if tiny

        # Load in a subprocess and track the high-water of ANONYMOUS
        # memory (RssAnon) via a sampling thread.  ru_maxrss is useless
        # here: it counts file-backed pages of mapped libraries, and
        # how much of libtorch becomes resident at import depends on
        # page-cache heat (~400MB cold vs ~1.3GB hot) — context noise
        # an order of magnitude above the signal.  A naive loader holds
        # the full f32 state dict (= file_bytes anon) for the whole
        # conversion, seconds long — a 5ms sampler cannot miss it.
        probe = (
            "import os, sys, json, threading, time\n"
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import jax.numpy as jnp, numpy as np, torch, safetensors\n"
            "from dlrover_tpu.models import hf_convert\n"
            "def anon():\n"
            "    with open('/proc/self/status') as f:\n"
            "        for line in f:\n"
            "            if line.startswith('RssAnon'):\n"
            "                return int(line.split()[1]) * 1024\n"
            "    return 0\n"
            "jnp.zeros((1024, 1024)).block_until_ready()\n"
            "torch.zeros(8).float().numpy()\n"
            "base = anon()\n"
            "hw = [base]\n"
            "stop = threading.Event()\n"
            "def sample():\n"
            "    while not stop.is_set():\n"
            "        hw[0] = max(hw[0], anon())\n"
            "        time.sleep(0.005)\n"
            "t = threading.Thread(target=sample, daemon=True)\n"
            "t.start()\n"
            f"params, cfg = hf_convert.from_hf_llama_dir({str(tmp_path)!r}, "
            "dtype=jnp.bfloat16)\n"
            "stop.set(); t.join()\n"
            "hw[0] = max(hw[0], anon())\n"
            "print(json.dumps({'delta': hw[0] - base, 'base': base, "
            "'peak': hw[0]}))\n"
        )
        # Minimal env built from scratch: the inherited environment
        # carries TPU/XLA state that skews the child's allocator
        # behavior and RSS in ways unrelated to the loader under test.
        env = {
            "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", "/root"),
            "PYTHONPATH": os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ),
            "JAX_PLATFORMS": "cpu",
        }
        out = subprocess.run(
            [sys.executable, "-c", probe], capture_output=True,
            text=True, env=env, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        probe_out = json.loads(out.stdout.strip().splitlines()[-1])
        delta = probe_out["delta"]
        # Output tree (bf16) = file_bytes/2; streaming adds ~one tensor
        # (<= 3MB here) + allocator slack.  A full f32 state-dict
        # materialization adds >= file_bytes on top -> >= 1.5x.
        assert delta < 1.0 * file_bytes, (
            f"peak delta {delta / 1e6:.0f}MB vs files "
            f"{file_bytes / 1e6:.0f}MB — not streaming ({probe_out}; "
            f"{out.stdout.strip().splitlines()[:-1]})"
        )
