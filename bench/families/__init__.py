"""Model families, one module each, chosen by the configuration file.

A configuration's ``"family"`` key (``"dense"`` where it has none) names
``bench/families/<family>.py``, looked for first under the root its cell
was loaded from (``spec.load_module``).  The module holds everything the
harness knows of that family and nothing else of it is hard-coded:

* ``program_config(cfg)``: the ``repro.models.common.ModelConfig`` that
  serves the configuration file ``cfg``;
* ``FAN_IN``: for each matrix leaf (without its ``layers/`` prefix), the
  indices of the axes it contracts over, after any layer axis
  (``bench/weights.py`` draws it with variance 1/fan-in; embeddings, norm
  scales and biases follow its generic rules);
* ``prefill_length(cfg, length)``: the length the program prefills a
  prompt of ``length`` tokens at (the warm-up compiles each such length);
* ``kv_bytes_per_token(cfg)``: the cache one token holds over all layers;
* ``decode_bytes(cfg, executions, contexts)``: the bytes ``executions``
  decode executions need, given the filled context of every row-step in
  them;
* ``token_flops(cfg, context, head=True)`` and ``prefill_flops(cfg,
  prompt)``: the operations of one token and of one prompt;
* ``COUNTERS``: the ``Endpoint`` attributes the drive loop reads at the
  window's markers (``metrics.Context.counter``).
"""
