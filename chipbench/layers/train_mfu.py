from chipbench.layer_tools import train_mfu as read  # train step: required fwd+bwd operations over chips x peak, at the median step
