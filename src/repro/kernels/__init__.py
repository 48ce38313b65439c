"""Pallas TPU kernels for the compute hot-spots.

Each kernel takes ``interpret``: compiled by Mosaic when False, run in the
Pallas interpreter when True.

* spike_prop/      block-gated synaptic delivery and the fused
                   delivery->LIF step (the paper's event-driven hotspot,
                   TPU-adapted as tile-granular activity gating).  The
                   ``blocked`` / ``blocked_fused`` engines compile it on a
                   TPU and interpret it on any other backend (the CPU
                   tests); tests/test_tpu_compile.py compiles it for v5e.
* lif/             LIF neuron update (float32 + int32 fixed-point); only
                   its tests call it, interpreted
* flash_attention/ online-softmax attention with causal/local masks
                   (LM-stack prefill hotspot; local-window block culling)
"""
