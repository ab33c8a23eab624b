"""Model families: ``families/<name>.py``, found by the ``family`` key of a
configuration file (``spec.Spec.family``).  A family is what the harness
knows of an architecture; everything else in ``bench/`` calls it through
these names alone:

  * ``check_program(cfg, cfg_file)``: raise unless the program's config
    object runs what the file states (the reference reads the file, so a
    drift would compare different models);
  * ``program_params(seed, cfg_file, model)``: the program's parameters,
    random from the seed, made on the device in one jitted call in the
    program's own tree (``weights.on_device``);
  * ``reference(cfg_file, seed, quant="none")``: the float32 reference at
    ``Precision.HIGHEST``, drawing the same weights again from the seed;
    ``quant="fp8"`` is its control.  It gives ``final_hidden(seqs)`` and
    ``head()``, ``vocab`` and ``q``, which ``reference.served_gaps`` reads;
  * ``yardstick(cfg_file)``: the benchmark's arithmetic on the file's
    sizes, with
      - ``vocab``: the real ids;
      - ``params()``: the parameters held on the chip;
      - ``prefill_flops(n)``: useful FLOPs of a prefill of ``n`` tokens;
      - ``decode_flops(contexts)``: of a decode step whose rows' new tokens
        attend to ``contexts`` positions each;
      - ``decode_least_time(step, peaks)``: (seconds, the bound that binds)
        of a decode step at the roofline; ``step`` is a ``cell.DecodeStep``,
        its contexts and the program's tracer events inside it;
      - ``kv_bytes(contexts)``: the KV that running requests of
        ``contexts`` tokens each hold.

A new family is a new file here; no file that is here needs an edit.
"""
