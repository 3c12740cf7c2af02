"""Share of its roofline reached by the packed varlen prefill attention
kernel (``kernels/varlen_prefill.py``) in the traced round: causal FLOPs
and q/k/v/o bytes of every prompt, from the served lengths
(``chipbench.work``), over the kernel's summed device time in the trace.

The trace names the kernel only by its HLO instruction, a Mosaic
``custom-call`` whose result is the packed query block
``bf16[kv_heads, budget * heads / kv_heads, head_dim]``; under tensor
parallelism each chip's kernel holds ``kv_heads / tp`` of the kv heads."""
from chipbench import work


def read(run):
    d = run.dims
    rows = int(run.cell.serve["serve"]["prefill_budget"]) * (d.heads // d.kv_heads)
    shape = f"= bf16[{d.kv_heads // run.tp},{rows},{d.head_dim}]"

    def match(text):
        return "tpu_custom_call" in text and shape in text

    return work.kernel_roofline(
        run, lambda dims, prompt, n: work.varlen_prefill(dims, prompt), match)
