from chipbench.layer_tools import decode_roofline_share as read  # decode tick: weights + live cache bytes over 819 GB/s, over the step time
