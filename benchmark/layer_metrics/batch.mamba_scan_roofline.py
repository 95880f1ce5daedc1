"""The selective scan's prompt-side kernel's (`mamba_scan`) share of its HBM
floor: the prompt tokens placed while the trace ran (`prefill_tokens`), a Mamba
layer each, times what the kernel must move for a token
(`decode_bytes/jamba_bytes.py::scan_token_bytes`: its rows of delta, delta u'
and y and its spread B and C), over the chips' HBM bandwidth, over the kernel's
device seconds in the prefill and extend programs. The kernel's work is on the
vector unit (a token costs some twenty vector operations a tile of 16 x 128
state numbers and only 78 KB), and `peaks.json` has no published peak of the
vector unit to hold it to, so this is its share of the floor that CAN be
stated, the bytes': it first read 45 % (PERF.md section 6, PR 54), a kernel
within about twice of being a question of bytes.
The pad rows of a bucket are moved and not counted. Over 100 % is a wrong
count. A program without the kernel (the parent of PR 54, or a piece that is
no whole number of 128 tokens) reads None."""
from harness.manifest import load_decode_bytes, load_layer_metric

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"

KERNEL = "mamba_scan"


def read(ctx):
    tr = ctx.get("trace")
    tokens = ((ctx.get("traced") or {}).get("counters") or {}).get("prefill_tokens")
    sizes = load_decode_bytes(ctx["model"])
    if not tr or not tokens or not hasattr(sizes, "scan_token_bytes"):
        return None
    modules = (load_layer_metric("extend.mamba_share.batch").PREFILL_MODULE,
               *load_layer_metric("step.extend_ms_per_ktok.batch").EXTEND_MODULES)
    seconds = sum(s for module in modules
                  for name, (_n, s) in tr["ops_in_module"].get(module, {}).items()
                  if name.split(".")[0] == KERNEL)
    if not seconds:
        return None
    moved = tokens * ctx["model"]["num_mamba_layers"] * sizes.scan_token_bytes(ctx["model"])
    return 100.0 * moved / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"]) / seconds
