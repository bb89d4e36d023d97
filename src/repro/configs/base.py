"""Model / training / DP configuration dataclasses."""
from __future__ import annotations

from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"        # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 128
    vocab: int = 256
    head_dim: int = 0            # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    act: str = "swiglu"          # swiglu | gelu
    rope_theta: float = 10000.0
    max_t: int = 4096            # rope table length (>= longest seq incl. cache)
    tie_embeddings: bool = False
    attn_chunk: int = 0          # q-chunked attention block (0 = full)
    seq_shard_attn: bool = False # context-parallel attention (q seq over 'model')
    seq_parallel: bool = False   # Megatron-SP: residual stream seq-sharded over 'model'
    remat: bool = False

    # MoE
    n_experts: int = 0           # routed experts: the router scores them all
    top_k: int = 0
    experts_held: int = 0        # routed experts this chip holds, from
    first_expert: int = 0        # first_expert on (0: all of them)
    n_shared: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0       # leading dense-FFN layers (DeepSeekMoE style)
    renorm_topk: bool = True

    # SSM / hybrid
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_chunk: int = 32          # wkv/ssm chunked-scan length
    window: int = 0              # sliding window for local attn layers
    full_attn_layers: tuple = () # hybrid: layer indices with global attention
    meta_tokens: int = 0         # Hymba learnable prefix tokens

    # enc-dec (whisper)
    encoder_layers: int = 0
    decoder_len: int = 448
    frame_dim: int = 0           # stub frontend embedding dim (0 -> d_model)

    # vlm
    patch_tokens: int = 0        # stub patch count for train shapes
    vit_dim: int = 0             # stub ViT output dim

    dtype: str = "float32"       # activations/compute
    param_dtype: str = "float32"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 8
    microbatch: int = 0          # physical batch per step (0 = global)
    seq_len: int = 128
    steps: int = 10
    lr: float = 1e-3
    lr_schedule: str = "cosine"
    optimizer: str = "adamw"
    weight_decay: float = 0.0
    warmup: int = 0
    # DP-FTRL (optimizer="ftrl"): momentum over noisy gradient prefixes,
    # epoch restarts every N steps (0 = never; also drives the tree-noise
    # mechanism's restarts), and Honaker tree completion at each restart
    ftrl_momentum: float = 0.0
    restart_every: int = 0
    tree_completion: bool = False
    seed: int = 0
    # mesh for the real train driver: (data, model) axis sizes; data=0 means
    # "all devices / model" (launch.mesh.make_train_mesh)
    mesh_data: int = 0
    mesh_model: int = 1
    # loss/timing log + device->host flush period in steps: the loop keeps
    # losses on device and drains them every log_every steps (and at exit),
    # so no step blocks on a host sync
    log_every: int = 10
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3
    # PrivacyPolicy preset name (configs.registry.get_policy):
    #   ""     — flat single-group DP from the DPConfig alone
    #   "auto" — use the arch's registered preset when one exists
    #   other  — a specific registered preset
    policy: str = "auto"
    # measured kernel autotune at startup (kernels.dispatch.autotune):
    #   "auto" — on for real accelerators, off on CPU (interpret mode)
    #   "on" / "off"
    autotune: str = "auto"
    # tape residency override (core.tape.TAPE_POLICIES): "" keeps whatever
    # the DPConfig / policy preset configured; tape_chunks 0 likewise
    tape: str = ""
    tape_chunks: int = 0
    # clipping-scope override (core.policy.SCOPES): re-scope every trainable
    # group of the DPConfig/preset via policy.with_scope — "layer" makes
    # each param path its own clip unit and streams the BK backward
    # (one pass, nothing book-kept); "" keeps the preset's scopes
    clipping_scope: str = ""


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
