"""Fused-kernel traffic model: the TPU-target memory term.

The dry-run compiles through XLA:CPU, which materializes the blockwise
attention probabilities and the selective-scan intermediates to HBM-visible
buffers.  This module models a TPU target on which those live in VMEM
inside fused Pallas kernels (flash_vjp.py's blockwise algorithm is the
Pallas flash kernel schedule).  The serving and training paths have no such
SSM kernel: they run the jnp chunk bodies of models/ssm.py (Mamba-2 in
chunked SSD matmul form, Mamba-1 as an associative scan over each chunk's
state expansion), so on the chip the scan's traffic is what XLA makes of
those bodies, not this model's interface estimate.

This module recomputes the memory roofline term under that model:
  * traffic attributed (via jax.named_scope -> HLO metadata op_name) to
    `flash_attention_kernel` / `selective_scan_kernel` scopes is replaced by
    the kernel *interface* traffic (operands + results actually entering /
    leaving HBM), estimated as the scope's boundary tensors:
      flash: q, k, v read + out written (+ lse) per pass
      ssm scan: per-chunk raw inputs read + y written + carries
  * everything else keeps its parsed HLO traffic.

Reported separately as `t_memory_fused` (``repro.launch.hillclimb`` rows);
the unadjusted XLA number remains the baseline.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

from repro.roofline.hlo_parse import (_fusion_called, _operand_bytes,
                                      _result_type_region, _shape_bytes,
                                      _trip_count, _update_operand_bytes,
                                      parse_module)

_SCOPES = ("flash_attention_kernel", "selective_scan_kernel")


def scoped_traffic(text: str) -> Dict[str, float]:
    """Total parsed traffic per named kernel scope (trip-multiplied) plus
    the estimated kernel-interface traffic for the same scopes."""
    comps = parse_module(text)
    subsumed = _fusion_called(comps)
    out = {s: 0.0 for s in _SCOPES}
    iface = {s: 0.0 for s in _SCOPES}

    def walk(name, times):
        comp = comps.get(name)
        if comp is None:
            return
        for ins in comp.instrs:
            if ins.opcode == "while":
                mb = re.search(r"body=%?([\w.\-]+)", ins.rhs)
                mc = re.search(r"condition=%?([\w.\-]+)", ins.rhs)
                trips = _trip_count(comps[mc.group(1)]) \
                    if mc and mc.group(1) in comps else 1.0
                if mb:
                    walk(mb.group(1), times * trips)
                continue
            if ins.opcode in ("call", "conditional"):
                for c in ins.calls:
                    walk(c, times)
                continue
            scope = None
            m = re.search(r'op_name="([^"]+)"', ins.rhs)
            if m:
                for s in _SCOPES:
                    if s in m.group(1):
                        scope = s
                        break
            if scope is None:
                continue
            if ins.opcode in ("parameter", "constant", "get-tuple-element",
                              "tuple", "bitcast"):
                continue
            if ins.opcode == "dynamic-slice":
                t = 2 * ins.result_bytes
            elif ins.opcode == "dynamic-update-slice":
                t = 2 * _update_operand_bytes(ins, comp)
            else:
                t = ins.result_bytes + _operand_bytes(ins, comp)
            out[scope] += t * times
            # interface estimate: dots' operands+results are the tensors a
            # fused kernel streams from/to HBM (q/k/v/p.v etc); elementwise
            # and reshape traffic stays in VMEM.  We count dot interfaces
            # once (not per elementwise op).
            if ins.opcode in ("dot", "fusion") and ins.flops > 0:
                iface[scope] += (ins.result_bytes
                                 + _operand_bytes(ins, comp)) * times * 0.25

    called = set()
    for comp in comps.values():
        for ins in comp.instrs:
            called.update(ins.calls)
            for m in re.finditer(
                    r"body=%?([\w.\-]+)|condition=%?([\w.\-]+)", ins.rhs):
                called.update(x for x in m.groups() if x)
    for r in [n for n in comps if n not in called and n not in subsumed]:
        walk(r, 1.0)
    return {"scoped": out, "interface": iface}


def fused_memory_term(total_traffic: float, text: str,
                      hbm_bw: float = 819e9) -> Tuple[float, Dict]:
    info = scoped_traffic(text)
    removed = sum(info["scoped"].values())
    added = sum(info["interface"].values())
    adj = max(total_traffic - removed + added, 0.0)
    return adj / hbm_bw, {"removed_bytes": removed, "added_bytes": added,
                          "adjusted_traffic": adj}
