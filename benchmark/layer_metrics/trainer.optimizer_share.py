"""Percent of the device's operation time under the train step's
``optimizer`` scope: the global-norm clip, AdamW's moments and the update's
application (device_trace joined with the compiled step's ``op_name``
scopes; ``Trainer._train_step_impl`` opens the scope). Nothing to read
where the program names no such scope."""

from benchmark import stepscopes, tracered


def read(record, trace):
    if trace is None or not record.get("hlo_scopes"):
        return None
    share = tracered.share_by_scope(trace, record["hlo_scopes"], stepscopes.in_step_scope("optimizer"))
    return share or None
