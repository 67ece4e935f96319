"""Serving layer: :mod:`repro.serve.placement`, the streaming
**placement** service (admission queue, micro-batched ``place_many``
windows, snapshot-epoch reads over a
:class:`~repro.core.engine.PlacementEngine`).
"""
