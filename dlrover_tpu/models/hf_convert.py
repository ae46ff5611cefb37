"""HuggingFace Llama checkpoint import.

A user of the reference fine-tunes HF checkpoints (the atorch llama2
example trains ``LlamaForCausalLM`` weights); this converter maps an HF
``LlamaForCausalLM`` state dict onto this framework's functional param
tree so those checkpoints train/serve here directly.

Layout notes (verified by the logit-parity test):
- torch ``nn.Linear`` stores ``[out, in]``; our projections are
  ``[in, out]`` -> every projection transposes.
- HF's rotary embedding is the split-half convention (rotate_half on
  ``[..., :D/2]`` / ``[..., D/2:]``) — identical to ``llama._rope``'s
  (d, d + D/2) pairing, so no permutation of head dims is needed.
- GQA: ``k_proj``/``v_proj`` carry ``KV * head_dim`` rows in the same
  [KV, head_dim] order our reshape expects.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    LlamaConfig,
    refuse_training_path_only,
)


def config_from_hf(hf_config: Any) -> LlamaConfig:
    """transformers ``LlamaConfig`` -> :class:`LlamaConfig`."""
    passes = int(getattr(hf_config, "total_ut_steps", 1) or 1)
    if passes > 1:
        raise ValueError(
            f"HF config has total_ut_steps={passes}: a looped model's "
            "checkpoint (four norms a layer, an exit gate) has no layout "
            "in this converter")
    derived_hd = int(hf_config.hidden_size) // int(
        hf_config.num_attention_heads
    )
    explicit_hd = getattr(hf_config, "head_dim", None)
    if explicit_hd is not None and int(explicit_hd) != derived_hd:
        raise ValueError(
            f"HF config has head_dim={explicit_hd} != hidden_size // "
            f"num_attention_heads = {derived_hd}; this LlamaConfig "
            "derives head_dim and cannot represent decoupled head dims"
        )
    return LlamaConfig(
        vocab_size=int(hf_config.vocab_size),
        n_layer=int(hf_config.num_hidden_layers),
        n_head=int(hf_config.num_attention_heads),
        n_kv_head=int(
            getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads
        ),
        d_model=int(hf_config.hidden_size),
        d_ff=int(hf_config.intermediate_size),
        max_seq_len=int(
            getattr(hf_config, "max_position_embeddings", 4096)
        ),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rms_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        sliding_window=int(getattr(hf_config, "sliding_window", 0) or 0),
    )


def _np(t) -> np.ndarray:
    try:  # torch tensor
        return t.detach().cpu().float().numpy()
    except AttributeError:
        return np.asarray(t, np.float32)


def config_from_hf_dir(path: str) -> LlamaConfig:
    """``config.json`` in an HF checkpoint dir -> :class:`LlamaConfig`."""
    import json
    import os
    import types

    with open(os.path.join(path, "config.json")) as f:
        d = json.load(f)
    return config_from_hf(types.SimpleNamespace(**d))


class _StreamingReader:
    """Per-tensor access to an HF checkpoint directory without ever
    materializing the whole state dict.

    safetensors files are read lazily (``safe_open`` + one
    ``get_tensor`` at a time, via torch so bf16 sources work);
    ``pytorch_model*.bin`` falls back to ``torch.load`` per shard —
    bounded by the shard size, not the checkpoint size."""

    def __init__(self, path: str):
        import json
        import os

        self.path = path
        self.weight_map: Dict[str, str] = {}
        # ONE shard handle at a time: a safetensors handle keeps its
        # file mmapped, and touched pages count toward RSS — caching
        # every shard's handle would re-materialize the whole
        # checkpoint's worth of resident pages, exactly what streaming
        # exists to avoid.  Dropping the old handle unmaps it.
        self._st_handle: Optional[Tuple[str, Any]] = None
        self._bin_cache: Optional[Tuple[str, Dict]] = None
        st_index = os.path.join(path, "model.safetensors.index.json")
        bin_index = os.path.join(path, "pytorch_model.bin.index.json")
        if os.path.exists(st_index):
            with open(st_index) as f:
                self.weight_map = json.load(f)["weight_map"]
        elif os.path.exists(os.path.join(path, "model.safetensors")):
            from safetensors import safe_open

            fname = "model.safetensors"
            with safe_open(
                os.path.join(path, fname), framework="pt"
            ) as h:
                self.weight_map = {k: fname for k in h.keys()}
        elif os.path.exists(bin_index):
            with open(bin_index) as f:
                self.weight_map = json.load(f)["weight_map"]
        elif os.path.exists(os.path.join(path, "pytorch_model.bin")):
            import torch

            fname = "pytorch_model.bin"
            sd = torch.load(
                os.path.join(path, fname), map_location="cpu",
                weights_only=True,
            )
            self._bin_cache = (fname, sd)
            self.weight_map = {k: fname for k in sd}
        else:
            raise FileNotFoundError(
                f"no model.safetensors[.index.json] or "
                f"pytorch_model.bin[.index.json] under {path!r}"
            )

    def keys(self):
        return self.weight_map.keys()

    def get(self, name: str) -> np.ndarray:
        import os

        fname = self.weight_map.get(name)
        if fname is None:
            raise KeyError(name)
        full = os.path.join(self.path, fname)
        if fname.endswith(".safetensors"):
            from safetensors import safe_open

            if self._st_handle is None or self._st_handle[0] != fname:
                self._st_handle = (fname, safe_open(full, framework="pt"))
            return _np(self._st_handle[1].get_tensor(name))
        import torch

        if self._bin_cache is None or self._bin_cache[0] != fname:
            # One .bin shard resident at a time.
            self._bin_cache = (
                fname,
                torch.load(full, map_location="cpu", weights_only=True),
            )
        return _np(self._bin_cache[1][name])


def _build_params(
    get: Any,  # (hf name) -> np.ndarray, raising KeyError when absent
    all_keys: Any,  # () -> iterable of raw checkpoint keys
    cfg: LlamaConfig,
    dtype,
    shardings: Any = None,
) -> Dict:
    """The single HF-Llama -> params layout table, shared by the
    in-memory and streaming importers (key names, transposes,
    tied-embedding fallback, bias rejection live HERE only)."""
    refuse_training_path_only(
        cfg, "the HF Llama layout table (models.hf_convert)")
    bias_keys = [k for k in all_keys() if k.endswith(".bias")]
    if bias_keys:
        raise ValueError(
            "HF checkpoint carries bias tensors this architecture has "
            f"no slot for (e.g. {bias_keys[0]!r}); converting would "
            "silently drop them"
        )

    def place(arr: jnp.ndarray, spec_path) -> jnp.ndarray:
        if shardings is None:
            return arr
        leaf = shardings
        for p in spec_path:
            leaf = leaf[p]
        import jax

        return jax.device_put(arr, leaf)

    def leaf(name: str, spec_path, transpose=False) -> jnp.ndarray:
        a = get(name)
        if transpose:
            a = a.T
        return place(jnp.asarray(a, dtype), spec_path)

    params: Dict = {
        "embed": leaf("embed_tokens.weight", ("embed",)),
        "ln_f": leaf("norm.weight", ("ln_f",)),
        "layers": [],
    }
    try:
        params["lm_head"] = leaf(
            "lm_head.weight", ("lm_head",), transpose=True
        )
    except KeyError:  # tied embeddings: reload rather than hold both
        params["lm_head"] = place(
            jnp.asarray(get("embed_tokens.weight").T, dtype),
            ("lm_head",),
        )
    for i in range(cfg.n_layer):
        p = f"layers.{i}."
        lp = ("layers", i)
        params["layers"].append({
            "ln1": leaf(p + "input_layernorm.weight", lp + ("ln1",)),
            "wq": leaf(p + "self_attn.q_proj.weight", lp + ("wq",),
                       transpose=True),
            "wk": leaf(p + "self_attn.k_proj.weight", lp + ("wk",),
                       transpose=True),
            "wv": leaf(p + "self_attn.v_proj.weight", lp + ("wv",),
                       transpose=True),
            "wo": leaf(p + "self_attn.o_proj.weight", lp + ("wo",),
                       transpose=True),
            "ln2": leaf(p + "post_attention_layernorm.weight",
                        lp + ("ln2",)),
            "mlp": {
                "w_gate": leaf(p + "mlp.gate_proj.weight",
                               lp + ("mlp", "w_gate"), transpose=True),
                "w_up": leaf(p + "mlp.up_proj.weight",
                             lp + ("mlp", "w_up"), transpose=True),
                "w_down": leaf(p + "mlp.down_proj.weight",
                               lp + ("mlp", "w_down"), transpose=True),
            },
        })
    return params


def from_hf_llama_dir(
    path: str,
    cfg: Optional[LlamaConfig] = None,
    *,
    dtype=jnp.bfloat16,
    shardings: Any = None,
) -> Tuple[Dict, LlamaConfig]:
    """Streaming import of an HF Llama checkpoint DIRECTORY.

    Unlike :func:`from_hf_llama` (which takes an in-memory model/state
    dict — fine for tests, ~4x the checkpoint in host RAM for a real
    7B), this loads ONE tensor at a time: read -> convert (transpose
    projections, cast to ``dtype``) -> optionally ``device_put`` onto
    the matching leaf of ``shardings`` (a params-tree of NamedSharding,
    e.g. ``job.state_sharding["frozen"]``) -> free before the next
    tensor.  Peak host RSS stays ~one tensor above the output tree (or
    ~one tensor total when placing straight to device), which is what
    lets a Llama-2-7B checkpoint load on one v5e host (the role of the
    reference's deferred/meta init,
    ``atorch/atorch/utils/meta_model_utils.py``)."""
    if cfg is None:
        cfg = config_from_hf_dir(path)
        import dataclasses

        cfg = dataclasses.replace(cfg, dtype=dtype)
    reader = _StreamingReader(path)

    def get(name: str) -> np.ndarray:
        for key in (name, f"model.{name}"):
            try:
                return reader.get(key)
            except KeyError:
                continue
        raise KeyError(
            f"HF checkpoint missing {name!r}; available keys start "
            f"with {sorted(reader.keys())[:3]}"
        )

    params = _build_params(get, reader.keys, cfg, dtype, shardings)
    return params, cfg


def from_hf_llama(
    model_or_state: Any,
    cfg: Optional[LlamaConfig] = None,
    *,
    dtype=jnp.float32,
) -> Tuple[Dict, LlamaConfig]:
    """(HF ``LlamaForCausalLM`` | its state_dict) -> (params, cfg).

    With a model, the config converts automatically; a bare state dict
    needs ``cfg``.  Tied embeddings (no ``lm_head.weight``) reuse the
    embedding transposed, matching HF's tie_word_embeddings."""
    import dataclasses

    if hasattr(model_or_state, "state_dict"):
        state = model_or_state.state_dict()
        if cfg is None:
            # Compute dtype follows the conversion dtype (the default
            # bf16 config under f32 weights would silently cost ~1e-3
            # of logit fidelity vs the source model).
            cfg = dataclasses.replace(
                config_from_hf(model_or_state.config), dtype=dtype
            )
    else:
        state = dict(model_or_state)
        if cfg is None:
            raise ValueError("a bare state dict needs an explicit cfg")

    def get(name: str) -> np.ndarray:
        for key in (name, f"model.{name}"):
            if key in state:
                return _np(state[key])
        raise KeyError(
            f"HF checkpoint missing {name!r}; keys start with "
            f"{sorted(state)[:3]}"
        )

    params = _build_params(get, lambda: state.keys(), cfg, dtype)
    return params, cfg
