"""Pallas TPU kernels (validated on CPU in tests via interpret=True):

* flash_attention — online-softmax attention (GQA, sliding window, softcap)
* ssd             — Mamba-2 SSD chunked scan with VMEM-carried state
* snapshot_patch  — fused base⊕diff restore (the paper's hot loop, on-TPU)
"""
