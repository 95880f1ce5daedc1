"""Share of the decode module's device seconds spent moving the cache
through the layer scan: the rows `_write_kv` writes (scope `kv.update`) and
what `lax.scan` itself emits for each layer, the slice of its K and V out
of the stacked cache and their write-back (`layers.scan_io`: no named
scope can be put around those, see `harness/spans.py`)."""
from harness import spans
from harness.layer_common import DECODE_MODULE

LAYER, UNIT, BETTER = "programs and model", "%", "lower"
SOURCE, MOVES = "device_trace", "gap_p95_ms"


def read(ctx):
    return spans.scope_share(ctx, DECODE_MODULE, "kv.update", spans.SCAN_IO)
