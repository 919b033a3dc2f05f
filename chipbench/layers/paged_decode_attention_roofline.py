from chipbench.layer_tools import paged_decode_attention_roofline as read  # kernels: live K/V bytes over 819 GB/s, over kernel time
