"""From a configuration file to the program's task params: the file is what
runs, for any family. The four sizes every file has (`model_dim`,
`num_heads`, `vocab_size`, the depth) and the logit cap are written onto the
registered model's params, `hidden_dim` where the file has one (a model with
no dense feed-forward has none to state), and then every key of the file's
optional `task_params` object, by name (a dotted name is a path into nested
params): KV heads, experts, a window, a layer pattern. A key the params do
not have fails the run and names it. After the task is built the cells read
the sizes that the roofline readers use back from the program (`ReadBack`,
`ReadBackPools`): which leaves of the decode state are page pools is the
program's word (`serving/state_layout.Detect`), what each ends in is the
file's.

Adding a configuration of another family adds a file with `task_params`
(and `num_kv_heads`, `attention_windows`, `pool_rows` where it has them),
its plain reference, and entries in BENCHMARK.json; nothing here is edited
(PERF.md section 4)."""

from __future__ import annotations

HEAD_SIZE_KEY = "dim_per_head"   # last part of a task_params path that is one
POOL_ROWS_KEY = "pool_rows"      # {pool leaf's last path key: its row}


def Sizes(config: dict, rehearse: bool) -> dict:
  """The sizes that run: the file's own, or its tiny `rehearsal` group (a
  shallow merge: a group that needs other `task_params` carries its own)."""
  if not rehearse:
    return config
  return {**config, **config["rehearsal"]}


def CheckHeads(sizes: dict) -> None:
  """The file's head arithmetic, where it is true. A file that writes no head
  size through `task_params` is of the family whose program derives
  `model_dim / num_heads`, and its `dim_per_head` must be that. A file that
  writes one states the same number under `dim_per_head`. `num_kv_heads`,
  where stated, divides `num_heads`. For a latent-attention file
  `dim_per_head` is the query-key head size the file states (`qk_head_dim`:
  `qk_nope_head_dim` + `qk_rope_head_dim`), the size the ragged roofline's
  products run over; what its pool keeps a token is not by heads at all and
  stands under `pool_rows` (`ReadBackPools`)."""
  d, n, h = sizes["model_dim"], sizes["num_heads"], sizes["dim_per_head"]
  written = {k: v for k, v in sizes.get("task_params", {}).items()
             if k.split(".")[-1] == HEAD_SIZE_KEY}
  for key, value in written.items():
    if value != h:
      raise ValueError(f"task_params {key!r} is {value!r} and dim_per_head "
                       f"{h!r}: the file states one head size")
  if not written and d != n * h:
    raise ValueError(
        f"dim_per_head {h!r} is not model_dim / num_heads = {d!r} / {n!r}, "
        f"and task_params writes no {HEAD_SIZE_KEY!r}: the program would "
        "derive another head size than the file states")
  kv = KvHeads(sizes)
  if kv < 1 or n % kv:
    raise ValueError(f"num_kv_heads {kv!r} does not divide num_heads {n!r}")


def ModelParams(sizes: dict, *, num_layers: int, flash: bool,
                remat_policy: str | None, input_seed: int):
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  from lingvo_tpu.core import attention as attention_lib
  import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)
  CheckHeads(sizes)
  mp = model_registry.GetParams(sizes["registry_model"], "Train")
  mp.input.Set(batch_size=sizes["batch_size"], seq_len=sizes["seq_len"],
               vocab_size=sizes["vocab_size"], seed=input_seed % (2**31))
  tp = mp.task
  tp.input = mp.input
  tp.Set(model_dim=sizes["model_dim"], num_heads=sizes["num_heads"],
         vocab_size=sizes["vocab_size"], num_layers=num_layers,
         softmax_logits_soft_max=sizes.get("logit_cap", 30.0))
  if "hidden_dim" in sizes:
    tp.hidden_dim = sizes["hidden_dim"]
  tp.fprop_dtype = jnp.bfloat16
  if remat_policy is not None:
    tp.remat_policy = remat_policy
  if flash:
    tp.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        use_flash_attention=True)
  LayTaskParams(tp, sizes.get("task_params", {}))
  return mp


def LayTaskParams(tp, task_params: dict) -> None:
  """Every key of the file's `task_params` onto `tp`, by name."""
  for key, value in task_params.items():
    try:
      tp.SetPath(key, value)
    except (AttributeError, TypeError) as e:
      raise KeyError(f"task_params key {key!r} is not a param of the "
                     f"registered model's task: {e}") from e


def Instantiate(task_p):
  task = task_p.Instantiate()
  task.FinalizePaths()
  return task


def _PathKeys(path) -> list:
  """A tree path's keys as strings (dict keys, attribute names, indices)."""
  return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def ReadBack(tree, what: str, ends_in, want: tuple) -> dict:
  """The file's word against the program's: every leaf of `tree` that
  `ends_in(path keys, shape)` picks has to end in the dimensions `want`.
  Returns {leaf path: shape} of the leaves read; no such leaf, or one of
  another shape, fails the run and says which."""
  import jax
  read = {}
  for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
    keys = _PathKeys(path)
    shape = tuple(leaf.shape)
    if not ends_in(keys, shape):
      continue
    read["/".join(keys)] = list(shape)
    if shape[-len(want):] != tuple(want):
      raise ValueError(
          f"{what} {'/'.join(keys)} has shape {shape}; the configuration "
          f"file states {tuple(want)} for its last {len(want)} dimensions")
  if not read:
    raise ValueError(f"the program has no {what} to read the file's "
                     f"{tuple(want)} back from")
  return read


def KvHeads(sizes: dict) -> int:
  return sizes.get("num_kv_heads", sizes["num_heads"])


def ReadBackQueryProjection(sizes: dict, theta) -> dict:
  """Train cells: every variable named `w_query` (the attention layer's
  query projection, [..., model_dim, heads, head size]) has the file's
  heads and head size."""
  return ReadBack(theta, "query projection",
                  lambda keys, shape: keys[-1] == "w_query",
                  (sizes["num_heads"], sizes["dim_per_head"]))


def PoolRows(sizes: dict) -> dict:
  """{a pool leaf's last path key: the dimensions it ends in behind its page
  and token-offset axes}, as the file states them: `key` and `value` are K
  and V by heads, `(num_kv_heads, dim_per_head)`, with no key of their own;
  any other leaf of a pool (a retention layer's gates, a latent row) stands
  under the file's optional `pool_rows`, which may also restate those two."""
  kv = (KvHeads(sizes), sizes["dim_per_head"])
  stated = {k: tuple(v) for k, v in sizes.get(POOL_ROWS_KEY, {}).items()}
  for k, row in stated.items():
    if not row or not all(isinstance(d, int) and d > 0 for d in row):
      raise ValueError(f"{POOL_ROWS_KEY} {k!r} is {list(row)!r}: a row is "
                       "one or more sizes")
  return {"key": kv, "value": kv, **stated}


def ReadBackPools(sizes: dict, task, theta, states) -> dict:
  """Serve cells: every page pool of the decode state ends in the row the
  file states. `states` is the engine's tree. Which of its leaves are pools,
  and on which axes their pages and token offsets lie, is the program's
  word: `state_layout.Detect` at the cell's own geometry (four abstract
  evaluations of `task.InitPagedDecodeState`, no device work), never a
  leaf's shape, so a slot state is not read whatever its dimensions. What a
  pool leaf has left once those two axes are taken out ends in its
  `PoolRows` entry. Returns {leaf path: shape} of the pool leaves. It fails
  the run, naming leaf, shape and stated row, where a row differs, where the
  program declares a pool leaf the file does not cover, where `pool_rows`
  states a leaf the program does not have, and where no leaf is a pool."""
  import jax
  from lingvo_tpu.serving import state_layout
  geo = sizes["serving"]
  layout = state_layout.Detect(task, theta, geo["num_pages"],
                               geo["page_size"], geo["max_batch"])
  flat = jax.tree_util.tree_flatten_with_path(states)[0]
  if len(flat) != len(layout.leaves):
    raise ValueError(f"the decode state has {len(flat)} leaves and the "
                     f"program's layout {len(layout.leaves)}")
  rows, stated = PoolRows(sizes), sizes.get(POOL_ROWS_KEY, {})
  read = {}
  for (path, leaf), axes in zip(flat, layout.leaves):
    if axes.page is None:
      continue
    keys = _PathKeys(path)
    name, shape = "/".join(keys), tuple(leaf.shape)
    if shape[axes.offset] != geo["page_size"]:
      raise ValueError(f"pool leaf {name} has shape {shape}: axis "
                       f"{axes.offset} is not the page size "
                       f"{geo['page_size']}")
    if keys[-1] not in rows:
      raise ValueError(
          f"pool leaf {name} has shape {shape} and the configuration file "
          f"states no row for {keys[-1]!r}: {POOL_ROWS_KEY} covers "
          f"{sorted(stated)}")
    want = rows[keys[-1]]
    rest = tuple(d for i, d in enumerate(shape)
                 if i not in (axes.page, axes.offset))
    if rest[-len(want):] != want:
      raise ValueError(
          f"pool leaf {name} has shape {shape}; the configuration file "
          f"states {want} for the last {len(want)} of the {rest} it keeps "
          f"behind its page axis {axes.page} and token-offset axis "
          f"{axes.offset}")
    read[name] = list(shape)
  if not read:
    raise ValueError("the program has no pool leaf to read the file's "
                     f"{rows['key']} back from")
  missing = sorted(set(stated) - {n.rsplit("/", 1)[-1] for n in read})
  if missing:
    raise ValueError(
        f"{POOL_ROWS_KEY} states {({k: stated[k] for k in missing})} and "
        f"the program declares no such pool leaf; its pools: {read}")
  return read
