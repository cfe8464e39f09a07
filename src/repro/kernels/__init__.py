"""Pallas TPU kernels for the framework's compute hot spots, each with a
pure-jnp oracle (ref.py) and a jitted wrapper (ops.py):

  flash_attention   -- online-softmax attention, VMEM scratch accumulator
  rwkv6_wkv         -- chunked WKV6 recurrence, state in VMEM scratch
  fedavg_agg        -- fused selection-weighted FedAvg aggregation (eq. 34)
  polyblock_project -- fused 60-step bisection projection of Algorithm 1
                       (eqs. 27-29), the control-plane hot spot (DESIGN.md §6);
                       ref.py here is NumPy (it doubles as the host solver's
                       projection), the jnp oracle lives in ops.project_jnp

On a TPU the wrappers compile the kernels to Mosaic.  Interpret mode
(kernel bodies executed op by op on the CPU) is the CPU test path only.
"""
