"""Jitted per-stage callables for the branch+fusion DAG pipeline.

``MultimodalStageFns`` is ``pipeline.stagefn.StageFns`` with the model hooks
of a ``MultimodalModel``; the builder, its module names
(``jit_stage{s}_F``/``_B``/``_dX``/``_W``) and the actor-runtime adapter
(``ActorStageProgram``) are the chain's.  The DAG's stages share no ``io``
tree, so each stage program is built with ``io = {}``, and a stage's ``x``
is the arrived payload:

* None at the source stages: the first encoder stage (its embeddings are in
  ``bm``) and the text stage (its tokens are in ``bm``);
* ``{src_stage: activation}`` at the fusion stage, one entry per incoming
  edge, so its backward emits one input gradient per branch;
* the upstream activation everywhere else.

**Shape bucketing.**  Encoder microbatches are variable-length; the batch
builder pads each one up to a bucket from a small fixed set, so jax's jit
cache retraces once per (stage, bucket) — the compile count is bounded by
the bucket count, not the number of distinct lengths (asserted by
``compile_cache_sizes`` in the bucketing tests).  The encoder forward is
bitwise padding-invariant (see ``multimodal.model``), so bucketed and
unbucketed execution produce identical loss bits.  Their gradient bits
currently differ (ROADMAP C1).
"""
from __future__ import annotations

from repro.pipeline.stagefn import StageFns


class MultimodalStageFns(StageFns):
    """Jitted forward/backward per stage of the branch+fusion pipeline."""

    def _stage_out(self, stage: int, sp_s, io, x, bm):
        model, cfg = self.model, self.model.cfg
        role = cfg.role_of(stage)
        if role == "encoder":
            return model.encoder_forward(
                stage, sp_s, bm["enc_embeds"] if stage == 0 else x,
                bm["enc_lens"])
        if role == "text":
            return model.text_forward(sp_s, bm["tokens"])
        if role == "fusion":
            return model.fusion_forward(sp_s, x[cfg.enc_stages - 1],
                                        bm["enc_lens"], x[cfg.text_stage])
        return model.lm_forward(sp_s, x)

    def _stage_loss(self, stage: int, sp_s, io, y, bm):
        return self.model.loss_sum(sp_s, y, bm["labels"])

    def microbatch(self, batch: dict, mb: int, stage: int) -> dict:
        """What stage ``stage`` reads of microbatch ``mb``, and no more: an
        operand a stage does not read would still key its jit cache (the
        encoder embeddings, by bucket)."""
        cfg, r = self.model.cfg, self.opts.mb_rows
        role = cfg.role_of(stage)
        bm = {}
        if stage == 0:
            bm["enc_embeds"] = batch["enc_embeds"][mb]
        if role in ("encoder", "fusion"):
            bm["enc_lens"] = batch["enc_lens"][mb]
        if role == "text":
            bm["tokens"] = batch["tokens"][mb * r:(mb + 1) * r]
        if stage == cfg.num_stages - 1:
            bm["labels"] = batch["labels"][mb * r:(mb + 1) * r]
        return bm

    def stage_graph(self):
        return self.model.cfg.stage_graph()

    def warm_microbatches(self, batch: dict) -> list[int]:
        """The first microbatch of each encoder bucket."""
        first: dict[tuple, int] = {}
        for mb, enc in enumerate(batch["enc_embeds"]):
            first.setdefault(enc.shape, mb)
        return sorted(first.values())

    # ---- bucketing observability ---------------------------------------
    def compile_cache_sizes(self) -> dict[tuple[str, int], int]:
        """Live jit-cache entry count per (op, stage): the number of
        distinct input shapes traced — bounded by the bucket count for the
        variable-length encoder/fusion stages."""
        return {k: f._cache_size() for k, f in self._jitted.items()}
