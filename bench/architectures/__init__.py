"""One module per model architecture: ``bench/architectures/<name>.py``.

A configuration file of ``bench/configs`` names its architecture under the
key ``"architecture"``; the harness loads that module by its file path
(``bench.harness.architecture``), so a later change brings a new
architecture as a new file and edits none.  The drivers, tools and
readers reach an architecture only through the module its cell's
configuration names.  A module provides:

``Arch``
    A frozen (hashable) dataclass of the sizes its reference needs, made
    by ``Arch.from_config(config)`` from the configuration's dict.  The
    drivers read two of its fields: ``num_layers``, the depth that WASH's
    layer schedule counts, and ``vocab_size``, the range tokens are drawn
    from.
``init_weights(key, arch)``
    One member's weights from ``key``, in the program's parameter layout
    and configured dtype, with every leaf the program's ``init_params``
    has (the drivers check the layout).  The tree's shape is the
    architecture's own.
``program_config(arch, name)``
    The program's ``ModelConfig`` for these sizes.
``logits_at(params, tokens, rows, arch)``
    The plain reference: float32 logits ``(len(rows), V)`` of one sequence
    at positions ``rows``.  It imports nothing of the program.
``loss(params, tokens, arch)``
    Mean next-token cross entropy of a batch ``(B, S)``, as the program's
    training loss defines it.  Training cells need it; a module without it
    serves only.
``control(arch)``
    The ``Arch`` whose reference is the control of ``correct``: the same
    forward at the nearest precision below the configuration's.
``train_step(arch, batch, seq)``
    Model FLOPs of one member's training step.
``prefill_chunk(arch, start, length)``
    Model FLOPs of a prompt chunk of ``length`` tokens at ``start``.
``decode_step(arch, lengths)``
    Model FLOPs of one decode step of slots whose contexts are
    ``lengths``.
``decode_kernel_flops(arch, lengths)``
    The paged-attention kernel's FLOPs in one decode step, over all layers.
``decode_kernel_bytes(arch, dmas, slots, page_size)``
    Its bytes: each layer's call copies ``dmas`` pages
    (``bench.flops.paged_attention_dmas``) and reads and writes each of
    ``slots`` slots' query and output once.

Counts follow ``bench/flops.py``: a multiply-add is 2 FLOPs, and only the
work the algorithm needs counts.
"""
