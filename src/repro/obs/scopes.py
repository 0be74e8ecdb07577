"""The layer scopes of the step programs.

Each name is opened with ``jax.named_scope`` at one layer boundary of the
model code. The scope lands in the HLO ``op_name`` metadata of every op the
layer emits, forward and backward, so a device profile's ops can be
attributed to the layer that made them:

=============  ==========================================================
``embed``      ``layers.embed``: the token lookup
``attention``  ``transformer.block_apply``: the pre-norm and
               ``layers.attention_block`` (weight casts, cache write)
``mlp``        ``transformer.block_apply``: the pre-norm and
               ``layers.mlp_block``
``mixer``      ``ssm.mamba_mixer``: norm, projections, conv, gate, output
``ssd``        ``ssm.ssd_chunked`` and ``ssm.ssd_decode`` (inside ``mixer``)
``lm_head``    ``layers.lm_head``: the final norm and ``layers.unembed``
``loss``       ``api.cross_entropy``
``optimizer``  ``optim.make_optimizer(...).step``, clipping included
=============  ==========================================================

Scopes change op metadata only, never the optimized program. This module
holds the names only: which op belongs to which scope is the reader's rule
(the benchmark's ``chipbench/attribution.py``). Stdlib only, like the rest
of ``repro.obs``.
"""

EMBED = "embed"
ATTENTION = "attention"
MLP = "mlp"
MIXER = "mixer"
SSD = "ssd"
LM_HEAD = "lm_head"
LOSS = "loss"
OPTIMIZER = "optimizer"

SCOPES = (EMBED, ATTENTION, MLP, MIXER, SSD, LM_HEAD, LOSS, OPTIMIZER)

