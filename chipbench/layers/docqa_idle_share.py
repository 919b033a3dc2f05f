from chipbench.layer_tools import idle_share as read  # device: share of the traced window with no operation running
