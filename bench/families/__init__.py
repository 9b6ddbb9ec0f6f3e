"""Model families, one module each: ``families/<family>.py``, named by a
configuration file's ``family`` key. A module turns that file's
published keys into what the harness needs of the model:

* ``arch_config(config, **overrides)``: the program's ``ArchConfig``;
* ``make_params(arch, seed)``: the seeded weights tree the program's
  decode step reads, made on the device in one jitted call;
* ``param_count(arch)``, ``layer_matmul_params(arch)``,
  ``kv_token_bytes(arch, dtype_bytes=2)``, ``decode_flops(arch, kv_lens)``
  and ``decode_bytes(arch, kv_lens, dtype_bytes=2)``: what a decode step
  needs, from shapes (the algorithm's needs, not what the
  implementation moves);
* ``reduced(config)``: the configuration at CPU-test widths, its flags
  and dtype kept.

``bench.cell``, ``bench.weights`` and ``bench.counts`` dispatch to the
module by the ``ArchConfig``'s ``family``, which is the configuration's
``family`` key (``bench.cell.arch_config`` checks that they agree);
adding a family adds a file here and edits none."""
