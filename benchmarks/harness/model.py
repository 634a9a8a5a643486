"""From a configuration file to the program's task params: the file is what
runs, so every size in it is written onto the registered model's params."""

from __future__ import annotations


def Sizes(config: dict, rehearse: bool) -> dict:
  """The sizes that run: the file's own, or its tiny `rehearsal` group."""
  if not rehearse:
    return config
  return {**config, **config["rehearsal"]}


def ModelParams(sizes: dict, *, num_layers: int, flash: bool,
                remat_policy: str | None, input_seed: int):
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  from lingvo_tpu.core import attention as attention_lib
  import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)
  mp = model_registry.GetParams(sizes["registry_model"], "Train")
  mp.input.Set(batch_size=sizes["batch_size"], seq_len=sizes["seq_len"],
               vocab_size=sizes["vocab_size"], seed=input_seed % (2**31))
  tp = mp.task
  tp.input = mp.input
  tp.Set(model_dim=sizes["model_dim"], num_heads=sizes["num_heads"],
         hidden_dim=sizes["hidden_dim"], vocab_size=sizes["vocab_size"],
         num_layers=num_layers,
         softmax_logits_soft_max=sizes.get("logit_cap", 30.0))
  if sizes["model_dim"] != sizes["num_heads"] * sizes["dim_per_head"]:
    raise ValueError("model_dim != num_heads * dim_per_head in the config")
  tp.fprop_dtype = jnp.bfloat16
  if remat_policy is not None:
    tp.remat_policy = remat_policy
  if flash:
    tp.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        use_flash_attention=True)
  return mp


def Instantiate(task_p):
  task = task_p.Instantiate()
  task.FinalizePaths()
  return task
