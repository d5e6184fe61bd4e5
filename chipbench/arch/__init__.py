"""Architectures: one module per architecture, ``arch/<name>.py``.

A configuration file names its architecture with the key ``"arch"``
(``"arch": "dense"`` is ``arch/dense.py``). ``cell.load`` loads that module
by path, as it loads a metric's reader, and hands it on as
``CellSpec.model`` and ``trace.Context.model``; the harness calls the
architecture only through it. So a new architecture is a new module here, a
configuration file that names it and entries in ``BENCHMARK.json``, with no
edit to any other file.

A module provides, where ``arch`` is what its own ``arch_of`` returned:

* ``arch_of(config) -> dict``: what the program, the reference and the
  counts need of a configuration file. It holds ``vocab_size`` (the traffic
  draws prompt tokens below it) and whatever else the module reads.
* ``program_config(arch)``: the program's ``ModelConfig`` at the
  configuration's sizes. It raises unless the program computes what the
  module's reference computes.
* ``param_tree(key, arch) -> dict``: the program's parameter tree, each
  leaf drawn in bfloat16 from the root key by ``chipbench.weights`` (``leaf``
  or ``stacked``, with the module's own leaf ids and draw kinds), traceable,
  so that ``serve.build_params`` draws, lays out and packs in one jitted call.
* ``token_gaps(arch, seed, tokens, targets, probe_ids)``: the plain float32
  reference over ``tokens`` ``[N, T]``, importing nothing of the program
  and drawing its weights again from the seed: per position, how far its
  logit of ``targets[:, t]`` lies below its best logit ``[N, T]``, its
  greedy token ``[N, T]`` and its logits at ``probe_ids`` ``[N, T, K]``.
* ``weight_gemms(arch, rows, head_rows=None) -> [(M, K, N)]``: every weight
  GEMM of one model step over ``rows`` token rows, the LM head's over
  ``head_rows`` rows where that is given (a prefill's last position).
* ``weight_shapes(arch) -> [(K, N)]``: the shape of every weight a GEMM
  multiplies by; the trace tells a GEMM kernel from other kernels by these.
* ``decode_token_flops(arch, position) -> int``: model operations of one
  token decoded at ``position``.
* ``prefill_flops(arch, length) -> int``: model operations of a prompt of
  ``length`` tokens, with the logits of its last position.
* ``kv_elements(arch, serving) -> (int, int)``: elements of one leaf of the
  paged KV pool and of the dense view the batched decode step reads from it.
"""
