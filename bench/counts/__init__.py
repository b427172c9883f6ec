"""The counts of one model family, one module per family, found by the
configuration's ``family`` (``bench.count.family``).  A module
``bench/counts/<family>.py`` defines, from the configuration and the
traffic mix alone:

* ``macs_per_item(cfg) -> int``: multiply-accumulates of one eval
  item's forward pass (an image, a sequence);
* ``eval_items(cfg, traffic) -> int``: the eval items of every row;
* ``program_lut_matmuls(cfg, traffic, layer) -> [(layer, M, K, N,
  shared_codes)]``: every LUT matmul launch of one banked program, per
  lane, over the whole eval set; ``layer`` None is the all-layers
  (Table II) program, a layer name the program that approximates that
  layer alone (Fig. 4).

They read nothing of the program.
"""
