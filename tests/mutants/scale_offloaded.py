"""Dequantize scale offloaded to host alongside the payload it scales.

The per-row fp32 scales must stay device-side: they are a few KB, and
the backward needs them immediately at dequantize time — pushing them
through the host channel adds a blocking reload to the critical path for
zero memory win.  This mutant (switch in ``runner.prefetch_chunk``) runs
``hostmem.to_host`` on the scale rows before naming them (and reloads
them before the backward's dequantize); the auditor's
R2 placement rule sees an ``act_scale@`` name whose producer is a
host-kind ``device_put`` and flags it (R1-d2h-count fires alongside —
the extra host puts also break the one-copy pairing count).
"""
CASE = dict(
    name="scale-offloaded",
    mutation="scale-offloaded",
    overrides={"offload_dtype": "fp8"},
    prefetch=None,
    expected_id="R2-scale-placement",
)
