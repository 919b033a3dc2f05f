from chipbench.layer_tools import decode_step_ms as read  # decode tick: decode-only step() wall over tick_block
