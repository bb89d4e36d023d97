# Fused Pallas kernel surface for the BK engine (plus model hot-spots).
#   mechanism: ghost_norm / grad_norm_direct / clipped_grad (mm taps),
#              emb_norm / emb_grad (embedding taps),
#              flash_attention, wkv6 — thin jit wrappers in ops.py
#   policy:    dispatch.py — per-tap kernel-vs-jnp choice + block autotune
#   contract:  ref.py pure-jnp oracles; tests/test_kernel_parity.py sweeps
