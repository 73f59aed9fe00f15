"""FLOPs the forward and backward pass require per step (from the
configuration's shapes) over step time, chips and the published peak."""


def read(facts):
    flops = facts["work_per_step"]["flops"] * facts["steps"]
    return 100.0 * flops / (facts["window_s"] * facts["chips"]
                            * facts["peaks"]["flops"])
