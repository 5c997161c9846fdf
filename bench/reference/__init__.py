"""Plain float32 reference forwards, one module per model family.

A configuration file names its family under ``reference``, and
``harness.reference_module`` imports ``reference.<family>``.  That
module is the only code of the benchmark that reads the family's own
keys of a configuration file (``m`` below: the file as a dict); the
harness, the check and the metric readers call it.  It provides:

- ``template(m)``: the served parameter tree, its names, shapes and
  fan-in scales; ``weights(m, seed)``: the weights the served engine
  draws from ``seed``, rebuilt by the engine's recipe (nothing taken
  from the program).  ``weights`` may return them in float32 or keep
  them in the stored type, to be upcast layer by layer inside
  ``forward``: then the reference of a configuration that nearly fills
  the chip still fits there once the pool is freed.
- ``forward(params, tokens, m, prompt_len, quant=None)``: the float32
  logits (S, vocab) of ``tokens`` (S,), the first ``prompt_len`` of
  which were served as the prompt; ``quant`` rounds the operands of
  each linear layer to the control's precision (``common.QUANT``).
- ``program_sizes(m)``: the program's ``ArchConfig`` attribute paths
  (``"d_model"``, ``"moe.n_experts"``, ...) and the values the file
  states for them; ``harness.matching`` refuses a program whose
  configuration differs in any.
- ``decode_step(m, context)``: (FLOPs, bytes) of one decoded token at
  batch 1 after ``context`` cached positions, counted from the file's
  shapes; ``metrics/decode_mfu.py`` reads it.

It may also count the operations and bytes of its own kernels, for
their roofline readers.  The keys every configuration file has beside
the published ones are in ``harness``'s docstring.
"""
