"""What the drivers share: the program's model configuration from a
configuration file, seeds, and host spans the trace can see."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

# HF-style keys of a configuration file -> fields of the program's ModelConfig
MODEL_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(conf: dict, ste: bool = False):
    """The program's ``ModelConfig`` for a configuration file: the program's
    own entry for ``conf["program_arch"]`` with every size the file states,
    and the file's CiM settings on every linear."""
    from repro.configs.registry import get_config
    from repro.core.cim_linear import CiMConfig

    fields = {field: conf[key] for key, field in MODEL_KEYS.items()}
    fields["param_dtype"] = fields["compute_dtype"] = conf["torch_dtype"]
    cim = conf.get("cim")
    if cim is not None:
        fields["cim"] = CiMConfig(**cim, ste=ste)
    return dataclasses.replace(get_config(conf["program_arch"]), **fields)


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A numpy generator for one use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, *tags]))


def span(name: str):
    """A host span the profiler records (``bench.*``); labels idle gaps."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def release() -> None:
    """Drop what the program keeps on the device between calls."""
    import gc

    from repro.launch.serve import compiled_model

    compiled_model.cache_clear()
    jax.clear_caches()
    gc.collect()
