"""A configuration file of ``chipbench/configs/`` read into the sizes the
benchmark's own code (weights, reference, operation counts) works from.

The configuration file is the configuration as it is run: published widths
under their Hugging Face key names, the depth of the weight-tied DEQ group
under ``num_hidden_layers``, and the solver settings under ``deq``.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    program_arch: str
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    blocks: int
    rope_theta: float
    norm_eps: float
    deq_weight_scale: float
    solver: str
    max_steps: int
    tol: float
    memory: int
    backward: str
    qn_dtype: str

    @property
    def padded_vocab(self) -> int:
        # the program pads the vocabulary to a multiple of 256 rows
        return -(-self.vocab // 256) * 256

    @property
    def block_params(self) -> int:
        """Parameters of one block of the DEQ group (norms included)."""
        d, ad, kvd = self.d, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * d + 2 * d * ad + 2 * d * kvd + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d * self.padded_vocab


def load(name: str) -> tuple[ModelSpec, dict]:
    """Read ``configs/<name>.json``; returns the spec and the raw file."""
    path = CONFIG_DIR / f"{name}.json"
    spec, raw = load_file(path)
    if spec.name != name:
        raise ValueError(f"{path} names itself {spec.name!r}")
    return spec, raw


def load_file(path: str | Path) -> tuple[ModelSpec, dict]:
    """Read a configuration file from any path."""
    raw = json.loads(Path(path).read_text())
    deq = raw["deq"]
    spec = ModelSpec(
        name=raw["name"],
        program_arch=raw["program_arch"],
        d=raw["hidden_size"],
        heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"],
        head_dim=raw["head_dim"],
        d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"],
        tied=raw["tie_word_embeddings"],
        blocks=raw["num_hidden_layers"],
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        deq_weight_scale=float(raw["assumed"]["deq_weight_scale"]["value"]),
        solver=deq["solver"],
        max_steps=deq["max_steps"],
        tol=deq["tol"],
        memory=deq["memory"],
        backward=deq["backward"],
        qn_dtype=deq["qn_dtype"],
    )
    return spec, raw
