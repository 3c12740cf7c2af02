"""Share of its roofline reached by the paged decode attention kernel
(``kernels/paged_attention.py``) in the traced round: KV bytes and FLOPs
of every decode step, from the served lengths (``chipbench.work``), over
the kernel's summed device time in the trace.

The trace names the kernel only by its HLO instruction, a Mosaic
``custom-call`` whose result is the grouped query block
``bf16[slots, kv_heads, heads / kv_heads, head_dim]``; under tensor
parallelism each chip's kernel holds ``kv_heads / tp`` of the kv heads."""
from chipbench import work


def read(run):
    d = run.dims
    shape = (f"= bf16[{run.slots},{d.kv_heads // run.tp},"
             f"{d.heads // d.kv_heads},{d.head_dim}]")

    def match(text):
        return "tpu_custom_call" in text and shape in text

    return work.kernel_roofline(run, work.paged_attention, match)
