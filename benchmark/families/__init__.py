"""What the harness knows of a model's shape, one family a file.

A configuration file names its `family`. `common.family(cfg)` loads
`benchmark/families/<family>.py` and, as its `reference`,
`benchmark/reference/families/<family>.py`, by path, as a metric's reader is
loaded; there is no default family. Adding a family adds those two files
(and a configuration that names it) and edits none: the tests' `layered`
is such a family, whole, under `benchmark/tests/` (they point
`common.FAMILIES`, the directory both files are looked for under, there). A layer is its index
in `range(cfg['num_hidden_layers'])`, -1 for what lies outside the layers;
a chip's share of a layer (experts held, rows of the vocabulary) is read
from the configuration's keys by every function alike.

`families/<family>.py`, the side that touches the program and the counts:

- `make_model(cfg, seed, max_positions)`: the program's own model class at
  the configuration's sizes as `jax.eval_shape` gives it, filled and
  checked by `weights.fill_model(sys.modules[__name__], cfg, struct,
  seed)`; refuses (`SystemExit`) what the program's class cannot run.
- `leaf_id(path)`: (layer, name) of a leaf of that model's pytree, from its
  path as `jax.tree_util.keystr` prints it.
- `layer_shapes(cfg, layer)`, `global_shapes(cfg)`: {leaf name: (shape,
  dtype)}; matrices are (in, out).
- `init(name, noise)`: a leaf's float32 values from standard-normal noise
  of its shape. `weights.make_leaf` alone folds seed, layer and name into
  the noise's key, for the program and for the reference.
- `layer_like(cfg, layer)`: the lowest index of a layer with the same
  leaves, shapes and equations. The reference calls `layer_shapes` and
  `layer_forward` with that index, so it is compiled once a kind of layer.
- the needed work of one token in one layer, as whole numbers:
  `matmul_params(cfg, layer)` (parameters of the matrices a token goes
  through: experts per token, not experts held), `head_params(cfg)`,
  `attn_keys(cfg, layer, context)` (keys a query at `context` attends,
  itself included; `context` may be an integer array),
  `attn_flops_key(cfg, layer)` (QK^T and PV of one query against one key),
  `cache_bytes_token(cfg, layer)` (what one position keeps for later
  queries), `query_bytes_token(cfg, layer)` (q read and the output
  written). `harness/model_flops.py` sums them over layers; a function of
  that module's name in the family's file takes its place, and a new
  kernel's `needed_<kernel>(ctx)` arrives in the family's file with its
  metric file.

`reference/families/<family>.py`, the plain float32 reference, which
imports `benchmark/reference/decoder.py`'s library and nothing of the
program:

- `embed(gp, ids)`, `layer_forward(cfg, lp, x, layer, quant)`,
  `logits(cfg, gp, x, quant)`: `lp` and `gp` are the layer's and the
  globals' leaves by name; `quant` is the control's rounding, handed to
  `decoder.linear`.
- `faults(cfg)`: which of `train_ref.run`'s planted faults a training cell
  of this configuration can have (`half_batch`, `frozen`, and
  `no_bias_grad` where leaves end in `_bias`).

`llama.py` is models/llama.py's `LlamaForCausalLM`: Mistral-7B-v0.3 and
Qwen2.5-3B.
"""
