"""The comparison that decides `correct`, part (a): the program's forward
through the cache against the plain reference, on logits.

In set-up, outside the window: a seeded sequence of prefill + decode
tokens. The program side is `forward` of the model module the
configuration names (`program.module` in its file; the defaults are
`harness/manifest.py`'s) with the engine's own parameters, its mesh and
the kernel route as served: one prefill of the first tokens into a cache,
then single-token decode steps through that cache (teacher-forced with
the seeded tokens, because with random weights the largest logit changes
on rounding). The reference side is one whole-sequence float32 forward of
the reference module the configuration names (`"reference"` in its file).

What the harness asks of the program's model module, and no more:

    init_params(cfg, key, dtype=) -> params     (harness/weights.py)
    param_specs(cfg) -> PartitionSpecs like params
    init_kv_cache(cfg, batch, rows, dtype=) -> cache, a tuple of arrays
    kv_cache_specs(kv_quant) -> PartitionSpecs like cache
    forward(params, cfg, tokens [B, T], positions [B, T], *cache,
            start [B], mesh=) -> (logits [B, T, V], *cache)

`cfg` is the program's ModelConfig, of which the harness itself reads
`vocab_size` and replaces `tie_embeddings` and the layers (below). The cache
is opaque: a tuple of any length (a pair of K and V, one latent array, a
pair and a state), made by the module, handed back to it whole and never
indexed here. Of `params` the harness knows `embed` [V, W] (also the head,
transposed, where the table is tied), `layers`, and `lm_head` [D, V] where
untied: that is what `_sub_model` cuts a one- or two-layer model from.

`layers` is either ONE tree whose every leaf is led by the layer axis (every
layer alike), or a SEQUENCE (list or tuple) of such trees, a *stack* for each
kind of layer, each with its own leading axis. A module of the second kind
says two things more:

    layer_order(cfg) -> ((stack, index), ...)   for model layer 0, 1, ...
    with_layer_order(cfg, order) -> cfg         the same model with those layers

`layer_order` is the order in which the model runs its layers: layer l is
`index` of `params["layers"][stack]`; a stack's layers run in the order of
its axis. "One dense layer, then five sparse" is ((0, 0), (1, 0), ... (1,
4)); "two of one kind, one of the other, twice" ((0, 0), (0, 1), (1, 0), (0,
2), (0, 3), (1, 1)). `with_layer_order` makes the ModelConfig of the model
that `_sub_model` cuts: `order` is that model's own order, over the cut
stacks, and the counts of every stack follow from it (a stack may be left
with no layer: its leaves then have a leading axis of 0). A module that
says neither has one tree, and `dataclasses.replace(cfg, num_layers=depth)`
is how its cut model's config is made, as before. The reference of such a
family states the same order from the configuration's file, on its own
(`layer_order(sizes)`), and `check` refuses a run in which the two differ.

The stream may be wider than the model. `_sub_model` makes the reference's
stream into layer l the cut model's `embed` table, whatever its width W: a
family that carries n copies of the residual a token gives `residual` as
[L + 1, T, n * D], and its module takes a table of that width as the
expanded stream itself (a table [V, D] it expands as it always does).
Nothing here reads a width.

The reference is handed those cut trees too, with `sizes` as they are but
for `layer_order`, the cut model's own order, where `layers` is a sequence:
it takes its depth from the tree (and its order from that key), never from
`sizes["config"]`, which is the file at full depth. The module named is the
one the engine dispatches to (`manifest.served_by`; `run.py` refuses a
configuration that names another), so what is judged here is what the
window times.

Tolerance, as shares of the reference's logit range (max - min): max
|diff| <= 5e-2 and mean |diff| <= 1e-2. PR 22 measured one bf16 run at
0.098-0.124 absolute from an f32 "highest" evaluation on logits of range
~4.6, that is 2.1e-2 to 2.7e-2 of the range at its worst element, mean
3.5e-3 to 4.1e-3 (PERF.md section 6); the bound is about twice that. A
wrong mask, a wrong RoPE, a dropped expert or a cache row out of place
moves logits by tenths of the range, far outside it.

A dense model (`num_experts` 0) is held to that at every position of 128
prefill + 8 decode tokens, as it was before sparse models were judged.

A model with a router cannot be. Where the k-th and the (k+1)-th router
logit nearly tie, bf16 noise in the hidden state picks the other expert,
and one flip swaps a whole expert's output at that position: tenths of the
range, with nothing wrong in the program. Worse, the flipped position is
attended to by every later one in every later layer, which flips more: at
Mixtral-8x7B's widths and 12 layers a sound, dropless bf16 evaluation on
the chip has its *median* position 8e-2 to 11e-2 of the range from the
reference, and positions whose own routing is decided in every layer up to
0.49 (PERF.md section 6, PR 27). So a sparse model is judged three times, never at its whole
depth:

- *Layer by layer, teacher-forced.* The reference gives the stream that
  enters each layer (`forward_routed`: `residual`). Each layer alone is then
  a one-layer model whose embedding table is that stream, rounded to the
  served type, and whose tokens are the positions: the program is handed
  parameters and nothing inside it is touched. It runs that model through
  a one-layer cache, prefill and decode as above; the reference runs the
  same one-layer model in float32, which also says how decided each
  routing decision was in its own router (`margin`, `sigma`). A flip now
  moves its own position and no other, so the *decided* (layer, position)
  pairs, margin >= TAU_SIGMA of that layer's router-logit sigma, are held
  to the same MAX_TOL and MEAN_TOL as a dense model, prefill and decode
  alike; there must be MIN_DECIDED of them; the undecided ones over MAX_TOL
  are counted and reported, not judged. A dropped token, a wrong combine
  weight or a wrong expert is tenths of the range at every pair it
  touches, decided or not.
- *Against rounding's own share.* One layer's rounding is a small part of
  logits that the stream dominates, ever smaller with depth, so MAX_TOL
  and MEAN_TOL would let weights of half the precision through. The
  reference therefore runs each one-layer model once more in the served
  type (`compute=`): plain code, the same roundings a sound evaluation
  cannot avoid. On the decided pairs of each layer the program's mean
  distance from float32 may be at most NOISE_FACTOR times that
  evaluation's. The ratio does not know the depth, the width or the range.
- *The first two layers together.* What a layer alone cannot show is
  what joins layers: the order of the scan and the cache's layer index
  (with one leading layer of another kind: the join between two stacks and
  the cache's index across them).
  Layers 0 and 1 are therefore run once more as a two-layer model on the
  embedded tokens, through a two-layer cache, against the same two layers
  in float32. Two layers is the depth at which this can be judged at all:
  a flip in layer 0 spoils its own position in layer 1 and reaches the
  others only through attention, diluted, so a few positions in a hundred
  are far off and the rest sit at rounding; with every further layer more
  are (the whole depth of 12 has its median position past MAX_TOL, see
  above), and no limit on the whole depth separates sound from wrong. The
  median position's worst logit, prefill and decode each, is held to
  PAIR_TOL. Layers in the wrong order or a cache read at the wrong layer
  move every position by half the range.

Every model layer of every stack is judged so, in the model's order. A
layer without a router has nothing to flip: its reference reports an
infinite margin (every position decided) and it is held to the same three
limits as any decided pair.

Nothing is fed from the program into the reference: no routing is forced
and no expert choice is read from the served side.

TAU_SIGMA, NOISE_FACTOR, PAIR_TOL and MIN_DECIDED are set from
readings on the chip at Mixtral-8x7B's published widths, sound runs'
largest and the controls' smallest on both sides of each (PERF.md section
6, PR 27).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.manifest import (DEFAULT_MODEL_MODULE, DEFAULT_REFERENCE, load_model_module,
                              load_reference)

PREFILL, DECODE, CACHE_ROWS = 128, 8, 256
MAX_TOL, MEAN_TOL = 5e-2, 1e-2

# Sparse configurations (see the docstring; readings in PERF.md section 6, PR 27).
# Least margin, in sigmas of the layer's router logits, at which a routing
# decision counts as decided.
TAU_SIGMA = 0.1
# Fewer decided (layer, position) pairs than this and their maximum says little.
MIN_DECIDED = 48
# A layer's mean distance from float32 on its decided pairs, over that of the
# plain evaluation in the served type.
NOISE_FACTOR = 1.8
# Layers in the model that shows the scan's order and the cache's layer index,
# and the most its median position's worst logit may be off, as a share of
# the range: sound runs read 5e-3 to 2e-2 (flips in layer 0 reach the other
# positions through attention, more in some seeds than in others), the two
# layers in the wrong order 0.53 to 0.62.
PAIR, PAIR_TOL = 2, 0.1


def _stacked(layers) -> bool:
    return isinstance(layers, (list, tuple))


def layer_order(model, cfg, layers):
    """((stack, index), ...) for model layer 0, 1, ... where `layers` is a
    sequence of stacks (the module's `layer_order(cfg)`, held to what the
    stacks hold); None for one tree, whose layers are its axis."""
    if not _stacked(layers):
        return None
    order = tuple((int(s), int(i)) for s, i in model.layer_order(cfg))
    held = [jax.tree_util.tree_leaves(stack)[0].shape[0] for stack in layers]
    named = [[i for s, i in order if s == k] for k in range(len(layers))]
    if named != [list(range(n)) for n in held] or not all(held):
        raise ValueError(
            f"{model.__name__}.layer_order names {named} of stacks that hold {held} "
            f"layers: every layer once, a stack's layers in the order of its axis, "
            f"and no stack without a layer")
    return order


def cut_layers(order, first: int, count: int):
    """Where model layers `first` to `first + count - 1` lie: (each stack's
    first layer among them, each stack's number of them, the cut model's own
    order over the cut stacks). A stack's layers run in the order of its
    axis, so those of one stack are a slice of it, possibly empty."""
    taken = order[first:first + count]
    stacks = 1 + max(s for s, _ in order)
    los = tuple(min((i for s, i in taken if s == k), default=0) for k in range(stacks))
    counts = tuple(sum(s == k for s, _ in taken) for k in range(stacks))
    return los, counts, tuple((s, i - los[s]) for s, i in taken)


def cut_config(model, cfg, layers):
    """The ModelConfig of a model `_sub_model` cuts: `layers` is its depth
    (one tree) or its own order over the cut stacks (`cut_layers`)."""
    if isinstance(layers, int):
        return dataclasses.replace(cfg, num_layers=layers, tie_embeddings=False)
    return dataclasses.replace(model.with_layer_order(cfg, layers), tie_embeddings=False)


def _sub_model(params, stream, first, count, dtype):
    """The parameters of the model that is layers `first` to `first + count
    - 1` alone on `stream` [T, W]: the stream is its embedding table
    (position t is token t), the final norm and the head stay. Where
    `layers` is a sequence of stacks, `first` and `count` are sequences
    too, a stack's first layer and number of layers kept (`cut_layers`).
    Traced inside a jit; `first` may be traced, `count` is static."""
    def cut(stack, lo, n):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, lo, n, axis=0), stack)

    if _stacked(params["layers"]):
        layers = [cut(*each) for each in zip(params["layers"], first, count, strict=True)]
    else:
        layers = cut(params["layers"], first, count)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return {**params, "embed": stream.astype(dtype), "layers": layers, "lm_head": head}


def _cut(order, n: int, depth: int):
    """The model of `depth` layers from model layer n, as (first, count) for
    `_sub_model` and what `cut_config` takes; `order` None is one tree."""
    return (n, depth, depth) if order is None else cut_layers(order, n, depth)


def _cuts(order, layers: int, depth: int):
    """`_cut` for every n < `layers`, with `first` as arrays, so that cuts
    alike but for where they start share one compiled program."""
    return [(jax.tree_util.tree_map(jnp.int32, first), count, cut)
            for first, count, cut in (_cut(order, n, depth) for n in range(layers))]


def _served_logits(engine, model_cfg, tokens, prefill: int, cache_rows: int,
                   layer_inputs=None, depth: int = 1,
                   model_module: str = DEFAULT_MODEL_MODULE):
    """The program's logits as float32 for `tokens` through a fresh cache.
    Whole depth: [T, V]. With `layer_inputs` [N, T, W], the stream the
    reference saw enter layer n: [N, T, V], each the `depth` layers from n
    alone (`_sub_model`; `tokens` is then arange(T))."""
    from omnia_tpu.parallel import init_sharded

    model = load_model_module(model_module)
    mesh = engine._mesh  # the mesh the engine's parameters live on
    dtype = engine.params["embed"].dtype

    def fresh(cfg):
        return tuple(init_sharded(
            lambda: model.init_kv_cache(cfg, 1, cache_rows, dtype=dtype),
            model.kv_cache_specs(None), mesh))

    def stepper(cfg):
        def step(params, cache, toks, start):
            pos = start + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
            logits, *cache = model.forward(params, cfg, toks, pos, *cache,
                                           jnp.reshape(start, (1,)), mesh=mesh)
            return logits, tuple(cache)
        return step

    def through_cache(jitted, cache, *lead):
        """One prefill of `prefill` tokens, then one token a step."""
        served = []
        for lo, hi in [(0, prefill)] + [(i, i + 1) for i in range(prefill, len(tokens))]:
            logits, cache = jitted(*lead, cache, jnp.asarray(tokens[None, lo:hi]),
                                   jnp.int32(lo))
            served.append(np.asarray(logits[0], np.float32))
        return np.concatenate(served, axis=0)

    if layer_inputs is None:
        return through_cache(jax.jit(stepper(model_cfg)), fresh(model_cfg), engine.params)

    @functools.cache
    def program(count, layers):
        """The compiled program of one kind of cut, and its fresh cache."""
        cfg = cut_config(model, model_cfg, layers)
        step = stepper(cfg)

        def sub_step(params, stream, first, *rest):
            return step(_sub_model(params, stream, first, count, dtype), *rest)

        return jax.jit(sub_step), fresh(cfg)

    order = layer_order(model, model_cfg, engine.params["layers"])
    return np.stack([
        through_cache(*program(count, layers), engine.params, layer_inputs[n], first)
        for n, (first, count, layers) in enumerate(
            _cuts(order, layer_inputs.shape[0], depth))])


def _seeded_tokens(model_cfg, seed: int, n: int):
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0FFEE])
    return rng.integers(0, model_cfg.vocab_size, size=n).astype(np.int32)


def check(engine, model_cfg, sizes: dict, seed: int,
          reference: str = DEFAULT_REFERENCE,
          model_module: str = DEFAULT_MODEL_MODULE) -> dict:
    ref_mod = load_reference(reference)
    if sizes["num_experts"]:
        return _check_sparse(engine, model_cfg, sizes, seed, ref_mod, model_module)

    tokens = _seeded_tokens(model_cfg, seed, PREFILL + DECODE)
    served = _served_logits(engine, model_cfg, tokens, PREFILL, CACHE_ROWS,
                            model_module=model_module)
    ref = jax.jit(lambda params, toks: ref_mod.forward(params, sizes, toks))(
        engine.params, jnp.asarray(tokens))
    ref = np.asarray(ref, np.float32)

    rng_ = float(ref.max() - ref.min())
    diff = np.abs(served - ref)
    out = {"logit_range": rng_}
    for name, sl in (("prefill", slice(0, PREFILL)), ("decode", slice(PREFILL, None))):
        out[f"{name}_max_over_range"] = float(diff[sl].max() / rng_)
        out[f"{name}_mean_over_range"] = float(diff[sl].mean() / rng_)
    out["ok"] = bool(
        np.isfinite(served).all()
        and max(out["prefill_max_over_range"], out["decode_max_over_range"]) <= MAX_TOL
        and max(out["prefill_mean_over_range"], out["decode_mean_over_range"]) <= MEAN_TOL
    )
    return out


def compared(out: dict) -> dict:
    """name -> [number, limit] for every number of `check`'s result that was
    held to a limit (`decided_positions` to a least, the others to a most)."""
    limits = out.get("limits") or {"max_over_range": MAX_TOL, "mean_over_range": MEAN_TOL}
    pairs = {}
    for name, value in out.items():
        key = name.replace("prefill_", "").replace("decode_", "")
        limit = limits.get(key, limits.get(key + "_min"))
        if limit is not None:
            pairs[name] = [value, limit]
    return pairs


def decided_pairs(margin, sigma, tau: float = TAU_SIGMA):
    """[L, T] bool: the (layer, position) pairs whose routing the reference
    calls decided. `margin` [L, T], `sigma` [L], as `forward_routed` gives them."""
    margin, sigma = np.asarray(margin, np.float64), np.asarray(sigma, np.float64)
    return margin / sigma[:, None] >= tau


def _over_range(served, ref):
    """(worst, mean) [..., T]: each position's largest and mean |diff| as a
    share of the reference's logit range, a range for each leading index."""
    span = ref.max(axis=(-2, -1), keepdims=True) - ref.min(axis=(-2, -1), keepdims=True)
    diff = np.abs(served - ref) / span
    return diff.max(axis=-1), diff.mean(axis=-1)


def judge_sparse(layers_served, layers_ref, layers_plain, decided, prefill: int,
                 pair_served=None, pair_ref=None) -> dict:
    """The numbers compared and the verdict. Pure numpy. `layers_*` [L, T,
    V]: each layer alone on the reference's input to it; `pair_*` [T, V]:
    the first PAIR layers together (None for a model of one layer);
    `*_served` by the program, `*_ref` by the reference in float32,
    `*_plain` by the reference in the served type; `decided` [L, T] from
    `decided_pairs`."""
    worst, mean = _over_range(layers_served, layers_ref)          # [L, T]
    _, plain_mean = _over_range(layers_plain, layers_ref)
    out = {"tau": TAU_SIGMA, "decided_positions": int(decided.sum()),
           "decided_share": float(decided.mean())}
    compared = [out["decided_positions"] >= MIN_DECIDED, bool(np.isfinite(layers_served).all())]
    stretches = (("prefill", slice(0, prefill)), ("decode", slice(prefill, None)))
    for name, sl in stretches:
        d = decided[:, sl]
        if d.any():  # a stretch with no decided pair has no maximum to hold
            out[f"layers_{name}_max_over_range"] = float(worst[:, sl][d].max())
            out[f"layers_{name}_mean_over_range"] = float(mean[:, sl][d].mean())
            compared.append(out[f"layers_{name}_max_over_range"] <= MAX_TOL)
            compared.append(out[f"layers_{name}_mean_over_range"] <= MEAN_TOL)
    ratios = [float(mean[l][d].mean() / plain_mean[l][d].mean())
              for l, d in enumerate(decided) if d.any()]
    if ratios:
        out["layers_noise_ratio_max"] = max(ratios)
        out["layers_noise_ratio_min"] = min(ratios)  # not judged
        compared.append(out["layers_noise_ratio_max"] <= NOISE_FACTOR)
    undecided = worst[~decided]
    out["undecided_over_tol_share"] = (
        float((undecided > MAX_TOL).mean()) if undecided.size else 0.0)
    if pair_served is not None:
        pair_worst, _ = _over_range(pair_served, pair_ref)        # [T]
        compared.append(bool(np.isfinite(pair_served).all()))
        for name, sl in stretches:
            out[f"pair_{name}_median_worst_over_range"] = float(np.median(pair_worst[sl]))
            compared.append(out[f"pair_{name}_median_worst_over_range"] <= PAIR_TOL)
        out["pair_over_tol_share"] = float((pair_worst > MAX_TOL).mean())  # not judged
    out["limits"] = {"layers_max_over_range": MAX_TOL, "layers_mean_over_range": MEAN_TOL,
                     "layers_noise_ratio_max": NOISE_FACTOR,
                     "pair_median_worst_over_range": PAIR_TOL,
                     "decided_positions_min": MIN_DECIDED}
    out["ok"] = bool(all(compared))
    return out


def _cut_sizes(sizes: dict, layers) -> dict:
    """`sizes` for a model `_sub_model` cuts (`layers` as `cut_config` takes
    it): the head is named, and stacks come with the cut model's own order."""
    sizes = {**sizes, "tie_embeddings": False}
    return sizes if isinstance(layers, int) else {**sizes, "layer_order": layers}


def reference_layers(ref_mod, params, sizes: dict, residual, order=None):
    """Each model layer alone on the stream the reference saw enter it, by
    the reference: (float32 logits [L, T, V], served-type logits [L, T, V],
    margin [L, T], sigma [L]) as numpy. `order` as `layer_order` gives it."""
    dtype = params["embed"].dtype
    positions = jnp.arange(residual.shape[1], dtype=jnp.int32)
    @functools.cache
    def program(count, layers):
        cut = _cut_sizes(sizes, layers)

        def one(params, stream, first):
            p = _sub_model(params, stream, first, count, dtype)
            logits, margin, sigma, _ = ref_mod.forward_routed(p, cut, positions)
            return logits, ref_mod.forward(p, cut, positions, compute=dtype), margin[0], sigma[0]

        return jax.jit(one)

    per = [program(count, layers)(params, residual[n], first)
           for n, (first, count, layers) in enumerate(_cuts(order, residual.shape[0] - 1, 1))]
    return tuple(np.stack([np.asarray(x[i], np.float32) for x in per]) for i in range(4))


def _check_sparse(engine, model_cfg, sizes: dict, seed: int, ref_mod,
                  model_module: str = DEFAULT_MODEL_MODULE) -> dict:
    if not hasattr(ref_mod, "forward_routed"):
        raise AttributeError(
            f"reference {ref_mod.__name__!r} has no forward_routed, which a "
            f"configuration with a router needs (benchmark/README.md)")
    order = layer_order(load_model_module(model_module), model_cfg, engine.params["layers"])
    stated = None if order is None else tuple(map(tuple, ref_mod.layer_order(sizes)))
    if stated != order:
        raise ValueError(
            f"the model module {model_module!r} runs its layers as {order}, and the "
            f"reference {ref_mod.__name__!r} reads {stated} from the configuration's file")
    tokens = _seeded_tokens(model_cfg, seed, PREFILL + DECODE)
    positions = np.arange(len(tokens), dtype=np.int32)
    dtype = engine.params["embed"].dtype
    whole_ref, _, _, residual = jax.jit(
        lambda params, toks: ref_mod.forward_routed(params, sizes, toks))(
            engine.params, jnp.asarray(tokens))
    layers_ref, layers_plain, margin, sigma = reference_layers(
        ref_mod, engine.params, sizes, residual, order)
    layers = _served_logits(engine, model_cfg, positions, PREFILL, CACHE_ROWS,
                            layer_inputs=residual[:-1], model_module=model_module)
    pair = pair_ref = None
    if residual.shape[0] - 1 >= PAIR:  # the model's layers, of every stack
        pair = _served_logits(engine, model_cfg, positions, PREFILL, CACHE_ROWS,
                              layer_inputs=residual[:1], depth=PAIR,
                              model_module=model_module)[0]
        first, count, cut = _cut(order, 0, PAIR)
        pair_ref = np.asarray(jax.jit(lambda params, stream: ref_mod.forward(
            _sub_model(params, stream, first, count, dtype), _cut_sizes(sizes, cut),
            jnp.asarray(positions)))(engine.params, residual[0]), np.float32)
    out = {"logit_range": float(whole_ref.max() - whole_ref.min())}
    out.update(judge_sparse(layers, layers_ref, layers_plain, decided_pairs(margin, sigma),
                            PREFILL, pair, pair_ref))
    return out
