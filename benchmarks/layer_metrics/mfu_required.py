"""Operations the forward and backward passes require per token (causal
attention at half, nothing recomputed) times tokens/s/chip over the peak."""


def Read(run):
  return 100.0 * run["flops_per_token"] * run["train_tok_s"] / (
      run["peak"].flops_bf16)
