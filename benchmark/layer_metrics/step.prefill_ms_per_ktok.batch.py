"""Device time of `jit_prefill_insert` per 1000 prompt tokens, in a closed loop whose prompts are long
enough that prefill is a large part of the device's time and the cell judges tokens/s/chip."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("step.prefill_ms_per_ktok.ttft50")
MOVES = "out_tokens_per_s_chip"
