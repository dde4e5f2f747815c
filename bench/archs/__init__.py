"""Architectures, one module per ``model_type``.

``spec.arch(model_type)`` finds ``bench/archs/<model_type>.py`` by the
``model_type`` of a configuration's file, so that a configuration brings
its architecture by added files alone.  A module provides:

  arch_config(conf)
      the program's ``ArchConfig`` for the configuration.
  serving_loader(conf, cfg, policy)
      a function of a key that makes the program's serving parameters on
      the device from the seed, quantized as
      ``launch/steps.quantize_serving_params`` quantizes them.  How the
      work is split is the module's: the dense decoders make the whole
      float32 tree in one jitted call; a model whose float32 does not fit
      one chip makes its stacked layers one at a time (``bench/weights``).
  reference(key, conf)
      a function that maps one sequence's tokens (S,) to its (S, V)
      float32 logits: the architecture's plain ``jax.numpy`` reference
      at ``highest`` precision.  Its weights are made again from the key;
      it reads none of the program's arrays.

and, for the training runner (``train_cell``, ``study``), optionally

  init_weights(key, conf)   the float32 weight tree the train step starts from
  loss(params, batch, conf) the reference's mean next-token cross-entropy
  sgd_readings(init, key, batches, conf, lr, momentum)
                            the reference's readings of the first steps
  leaf_norms(tree)          float32 norm of each leaf, in flatten order

Modules whose name starts with ``_`` hold shared code and name no type.
"""
