"""Pallas TPU kernels for the AIMC-simulation hot spots + pure-jnp oracles.

  aimc_mvm        — fused DAC -> int8 crossbar MAC -> noise -> ADC -> accumulate
                    (kernel v2: in-kernel PRNG noise, fused epilogue,
                    gate-fused multi-MVM stacks; v1 legacy entry kept)
  cprng           — counter-based Gaussian PRNG shared by kernel and oracle
                    (bit-identical noise from a scalar seed, no HBM tensor)
  flash_attention — chunked online-softmax attention (O(seq) memory)
  ops             — jit'd dispatch wrappers (impl = auto | ref | pallas_interpret | pallas_tpu;
                    auto = the kernel on a TPU, the oracle elsewhere)
  ref             — pure-jnp oracles (bit-identical math, the AIMClib "checker")
"""
