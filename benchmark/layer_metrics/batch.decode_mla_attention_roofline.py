"""The latent decode-attention kernel's (`decode_mla_attention`) share of
its roofline, bound by HBM: the cached rows of the live contexts read once,
at the bytes a row must hold (`[c | k_rope]`, 640 B a layer:
`decode_bytes/mla_moe_bytes.py`; the program's row is padded to 768 B, which
shows here as roofline lost), over the chips' HBM bandwidth, over the
kernel's device time for one step. The kernel and the byte counts are the
configuration's (`program.decode_kernel`, `decode_bytes`), so the arithmetic
is `decode_gqa_attention_roofline`'s."""
from harness.layer_common import variant_of

LAYER, UNIT, BETTER, SOURCE, read = variant_of("decode_gqa_attention_roofline")
MOVES = "out_tokens_per_s_chip"
