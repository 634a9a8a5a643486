"""Prompt and streamed tokens of the requests that finished inside the
window, over the window: PR 22's way of counting, kept beside serve_tok_s."""


def Read(run):
  return run["finished_tok_s"]
