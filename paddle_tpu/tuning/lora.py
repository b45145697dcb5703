"""LoRA adapter definition/injection for gpt/llama fine-tuning.

Low-rank deltas ``scaling * (x @ A^T @ B^T)`` are injected on the attention and
MLP projections of each decoder layer.  The base weights are frozen at
injection time, so ``Layer.functional_state()`` returns a params tree holding
*only* the adapter leaves — ``AsyncCheckpointManager`` then snapshots just the
tiny adapter tree during fine-tuning, and the same tree is what gets published
as a certified ``AdapterWeightSet`` for serving.

The canonical adapter tree (what ``adapter_state_dict`` emits and the serving
``AdapterBank`` consumes) is::

    {"0": {"qkv_proj": {"A": [r, in], "B": [out, r]}, ...}, "1": {...}, ...}

keyed by decoder-layer index then target-site name.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.tensor import apply
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..ops.lora import lora_matmul

GPT_TARGETS = ("qkv_proj", "out_proj", "linear1", "linear2")
LLAMA_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj",
                 "gate_proj", "up_proj", "down_proj")


@dataclass
class LoRAConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: Optional[Tuple[str, ...]] = None  # None = all sites for the arch
    init_std: float = 0.02

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"LoRA rank must be >= 1, got {self.rank}")
        if self.alpha <= 0:
            raise ValueError(f"LoRA alpha must be > 0, got {self.alpha}")
        if self.targets is not None:
            self.targets = tuple(self.targets)


class LoRALinear(Layer):
    """Wraps a linear projection with a trainable low-rank residual.

    ``lora_B`` starts at zero so the wrapped module is exactly the base
    projection until training moves it.
    """

    def __init__(self, base, rank, alpha, init_std=0.02):
        super().__init__()
        self.base = base
        w = _base_weight(base)
        in_f, out_f = int(w.shape[0]), int(w.shape[1])
        self.rank, self.alpha = int(rank), float(alpha)
        self.scaling = self.alpha / self.rank
        self.lora_A = self.create_parameter(
            [self.rank, in_f], default_initializer=I.Normal(0.0, init_std))
        self.lora_B = self.create_parameter(
            [out_f, self.rank], default_initializer=I.Constant(0.0))

    def forward(self, x):
        y = self.base(x)
        scaling = self.scaling

        def _delta(xv, Av, Bv):
            return (lora_matmul(xv, Av, Bv) * scaling).astype(xv.dtype)

        return y + apply(_delta, x, self.lora_A, self.lora_B)


def _base_weight(module):
    base = module.base if isinstance(module, LoRALinear) else module
    if not hasattr(base, "weight"):
        raise TypeError(f"LoRA target {type(base).__name__} has no weight")
    return base.weight


def _decoder_layers(model):
    """-> (list of decoder layers, arch name 'gpt'|'llama')."""
    if hasattr(model, "gpt"):
        return list(model.gpt.layers), "gpt"
    if hasattr(model, "llama"):
        return list(model.llama.layers), "llama"
    if hasattr(model, "layers"):
        layers = list(model.layers)
        if layers and hasattr(layers[0].self_attn, "qkv_proj"):
            return layers, "gpt"
        return layers, "llama"
    raise TypeError(f"cannot locate decoder layers on {type(model).__name__}")


def default_lora_targets(model) -> Tuple[str, ...]:
    _, arch = _decoder_layers(model)
    return GPT_TARGETS if arch == "gpt" else LLAMA_TARGETS


def _site_owner(layer, name, arch):
    """Resolve the module owning a target projection within a decoder layer."""
    if arch == "gpt":
        owner = layer.self_attn if name in ("qkv_proj", "out_proj") else layer
    else:
        owner = (layer.self_attn
                 if name in ("q_proj", "k_proj", "v_proj", "o_proj")
                 else layer.mlp)
    if not hasattr(owner, name):
        from ..nn.layer.moe import DroplessMoE
        if isinstance(owner, DroplessMoE):
            raise ValueError(
                f"LoRA target {name!r}: this model's FFN is a sparse-expert "
                "layer, and adapters on expert projections are not "
                "supported (stacked [E, in, out] weights, a row per "
                "assignment); name attention targets only, e.g. "
                'targets=("q_proj", "k_proj", "v_proj", "o_proj")')
        raise ValueError(f"unknown LoRA target {name!r} for arch {arch!r}")
    return owner


def target_sites(model, targets=None):
    """Per-decoder-layer dims of each target site.

    -> (list over layers of {site: (in_dim, out_dim)}, arch).  Raises if
    layers disagree on a site's dims (the stacked serving bank requires a
    homogeneous stack).
    """
    layers, arch = _decoder_layers(model)
    targets = tuple(targets) if targets else (
        GPT_TARGETS if arch == "gpt" else LLAMA_TARGETS)
    sites: List[Dict[str, Tuple[int, int]]] = []
    for layer in layers:
        dims = {}
        for name in targets:
            w = _base_weight(getattr(_site_owner(layer, name, arch), name))
            dims[name] = (int(w.shape[0]), int(w.shape[1]))
        sites.append(dims)
    for dims in sites[1:]:
        if dims != sites[0]:
            raise ValueError("LoRA target dims differ across decoder layers; "
                             "a stacked adapter bank requires homogeneous "
                             f"layers, got {dims} vs {sites[0]}")
    return sites, arch


def adapter_signature(model, rank, alpha=None, targets=None) -> dict:
    """JSON-serializable signature binding an adapter to its base model.

    Shipped inside the `AdapterWeightSet` manifest and compared (typed
    refusal) against the serving bank before a row load.
    """
    sites, arch = target_sites(model, targets)
    return {
        "arch": arch,
        "num_layers": len(sites),
        "rank": int(rank),
        "alpha": None if alpha is None else float(alpha),
        "targets": sorted(sites[0].keys()),
        "dims": {name: [int(i), int(o)] for name, (i, o) in
                 sorted(sites[0].items())},
    }


def inject_lora(model, config: LoRAConfig):
    """Freeze every existing parameter and wrap the target projections.

    Returns the (mutated) model.  After injection ``functional_state()``
    yields a params tree of only ``lora_A``/``lora_B`` leaves; everything
    else rides the buffers tree.
    """
    layers, arch = _decoder_layers(model)
    targets = config.targets or (GPT_TARGETS if arch == "gpt"
                                 else LLAMA_TARGETS)
    for _, p in model.named_parameters():
        p.trainable = False
        p.stop_gradient = True
    for layer in layers:
        for name in targets:
            owner = _site_owner(layer, name, arch)
            current = getattr(owner, name)
            if isinstance(current, LoRALinear):
                raise ValueError(f"LoRA already injected at {name!r}")
            setattr(owner, name, LoRALinear(current, config.rank,
                                            config.alpha, config.init_std))
    return model


def _iter_adapted_sites(model):
    layers, arch = _decoder_layers(model)
    for i, layer in enumerate(layers):
        for name in (GPT_TARGETS if arch == "gpt" else LLAMA_TARGETS):
            try:
                owner = _site_owner(layer, name, arch)
            except ValueError:
                continue
            module = getattr(owner, name, None)
            if isinstance(module, LoRALinear):
                yield i, name, module


def lora_parameters(model):
    """The trainable adapter parameters (feed these to the optimizer)."""
    out = []
    for _, _, module in _iter_adapted_sites(model):
        out.extend([module.lora_A, module.lora_B])
    return out


def adapter_state_dict(model) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    """Extract the canonical adapter tree (host numpy, float32)."""
    tree: Dict[str, Dict[str, Dict[str, np.ndarray]]] = {}
    for i, name, module in _iter_adapted_sites(model):
        tree.setdefault(str(i), {})[name] = {
            "A": np.asarray(module.lora_A.data, dtype=np.float32),
            "B": np.asarray(module.lora_B.data, dtype=np.float32),
        }
    if not tree:
        raise ValueError("model has no injected LoRA adapters")
    return tree


def load_adapter_state(model, tree):
    """Load a canonical adapter tree back into an injected model."""
    seen = 0
    for i, name, module in _iter_adapted_sites(model):
        entry = tree.get(str(i), {}).get(name)
        if entry is None:
            raise ValueError(f"adapter tree missing layer {i} site {name!r}")
        A = jnp.asarray(entry["A"], dtype=module.lora_A.data.dtype)
        B = jnp.asarray(entry["B"], dtype=module.lora_B.data.dtype)
        if A.shape != module.lora_A.data.shape or \
                B.shape != module.lora_B.data.shape:
            raise ValueError(
                f"adapter shape mismatch at layer {i} site {name!r}: "
                f"{A.shape}/{B.shape} vs "
                f"{module.lora_A.data.shape}/{module.lora_B.data.shape}")
        module.lora_A.data = A
        module.lora_B.data = B
        seen += 1
    if not seen:
        raise ValueError("model has no injected LoRA adapters")
    return model


def merge_adapter_delta(model):
    """Fold each adapter delta into its base weight (serving without a bank).

    After merging, the LoRA residual is zeroed so the wrapped module keeps
    producing the merged output.
    """
    for _, _, module in _iter_adapted_sites(model):
        w = module.base.weight
        dW = module.scaling * jnp.einsum(
            "ri,or->io", module.lora_A.data.astype(jnp.float32),
            module.lora_B.data.astype(jnp.float32))
        w.data = (w.data.astype(jnp.float32) + dW).astype(w.data.dtype)
        module.lora_B.data = jnp.zeros_like(module.lora_B.data)
    return model
