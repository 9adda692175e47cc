"""Read at commit fc4ccb9, the parent of PR 56, by the functions of
tests/seam_snapshots.py: (``SHARDINGS``) the ``PartitionSpec`` of every
parameter and optimiser-state leaf of the nine sequence configurations of
``benchmark/configs/`` at their published sizes on ``(pop, model)`` meshes
of 1x1, 1x4 and 2x4 devices, under the ONE global rule list
``parallel/mesh.py`` then held; (``BUILDS``) ``run_manifest()["config"]``
(its ``partition_rules`` apart), the gauges and the engine's chunk sizes of
the eleven builds of tests/test_policy_contract.py; (``RULES_JSON``) the
``partition_rules`` a manifest of a sharded run then carried.  Generated
there, pasted here, never edited by hand."""

SHARDINGS = {'granite-4.0-h-micro-1period 1x1': {'leaves': 520,
                                     'sha256': '3a4c7ad55ee75cddac65075a2edda119ffc4040c293a2b38d753084770007c71',
                                     'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                    'None)',
                                               '*/final_norm/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                   'None)',
                                               '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/mamba/A_log': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_bc_bias': 'PartitionSpec(None,)',
                                               '*/layer_*/mamba/conv_bc_kernel': 'PartitionSpec(None, '
                                                                                 'None, '
                                                                                 'None)',
                                               '*/layer_*/mamba/conv_x_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_x_kernel': 'PartitionSpec(None, '
                                                                                'None, '
                                                                                "'model')",
                                               '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/in_bc': 'PartitionSpec(None, '
                                                                        'None)',
                                               '*/layer_*/mamba/in_dt': 'PartitionSpec(None, '
                                                                        "'model')",
                                               '*/layer_*/mamba/in_x': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/in_z': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/norm_scale': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                           'None)',
                                               '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                     'None)',
                                               '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                     "'model')",
                                               '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                               'opt_state/0/count': 'PartitionSpec()'}},
 'granite-4.0-h-micro-1period 1x4': {'leaves': 520,
                                     'sha256': '3a4c7ad55ee75cddac65075a2edda119ffc4040c293a2b38d753084770007c71',
                                     'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                    'None)',
                                               '*/final_norm/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                   'None)',
                                               '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/mamba/A_log': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_bc_bias': 'PartitionSpec(None,)',
                                               '*/layer_*/mamba/conv_bc_kernel': 'PartitionSpec(None, '
                                                                                 'None, '
                                                                                 'None)',
                                               '*/layer_*/mamba/conv_x_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_x_kernel': 'PartitionSpec(None, '
                                                                                'None, '
                                                                                "'model')",
                                               '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/in_bc': 'PartitionSpec(None, '
                                                                        'None)',
                                               '*/layer_*/mamba/in_dt': 'PartitionSpec(None, '
                                                                        "'model')",
                                               '*/layer_*/mamba/in_x': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/in_z': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/norm_scale': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                           'None)',
                                               '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                     'None)',
                                               '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                     "'model')",
                                               '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                               'opt_state/0/count': 'PartitionSpec()'}},
 'granite-4.0-h-micro-1period 2x4': {'leaves': 520,
                                     'sha256': '3a4c7ad55ee75cddac65075a2edda119ffc4040c293a2b38d753084770007c71',
                                     'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                    'None)',
                                               '*/final_norm/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                   'None)',
                                               '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/mamba/A_log': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_bc_bias': 'PartitionSpec(None,)',
                                               '*/layer_*/mamba/conv_bc_kernel': 'PartitionSpec(None, '
                                                                                 'None, '
                                                                                 'None)',
                                               '*/layer_*/mamba/conv_x_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/conv_x_kernel': 'PartitionSpec(None, '
                                                                                'None, '
                                                                                "'model')",
                                               '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/in_bc': 'PartitionSpec(None, '
                                                                        'None)',
                                               '*/layer_*/mamba/in_dt': 'PartitionSpec(None, '
                                                                        "'model')",
                                               '*/layer_*/mamba/in_x': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/in_z': 'PartitionSpec(None, '
                                                                       "'model')",
                                               '*/layer_*/mamba/norm_scale': "PartitionSpec('model',)",
                                               '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                           'None)',
                                               '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                     'None)',
                                               '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                     "'model')",
                                               '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                   "'model')",
                                               '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                               '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                               'opt_state/0/count': 'PartitionSpec()'}},
 'joyai-llm-flash-5layers 1x1': {'leaves': 313,
                                 'sha256': '9e6a7208ba491b51918b1e0c8cb9c2a5ccd6f9cee56fe04ef8e7494f870b45a2',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/kv_a': 'PartitionSpec(None, '
                                                                  'None)',
                                           '*/layer_*/attn/kv_b': 'PartitionSpec(None, '
                                                                  "'model')",
                                           '*/layer_*/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q_a': 'PartitionSpec(None, '
                                                                 'None)',
                                           '*/layer_*/attn/q_b': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           '*/mtp/eh': 'PartitionSpec(None, '
                                                       "'model')",
                                           '*/mtp/embed_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/hidden_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/kv_a': 'PartitionSpec(None, '
                                                                    'None)',
                                           '*/mtp/layer/attn/kv_b': 'PartitionSpec(None, '
                                                                    "'model')",
                                           '*/mtp/layer/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/o': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/mtp/layer/attn/q_a': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/mtp/layer/attn/q_b': 'PartitionSpec(None, '
                                                                   "'model')",
                                           '*/mtp/layer/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/experts/down': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/gate': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/up': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/mtp/layer/moe/router': 'PartitionSpec(None, '
                                                                     'None)',
                                           '*/mtp/layer/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/shared/down': "PartitionSpec('model', "
                                                                          'None)',
                                           '*/mtp/layer/moe/shared/gate': 'PartitionSpec(None, '
                                                                          "'model')",
                                           '*/mtp/layer/moe/shared/up': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/mtp/layer/norm1/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'joyai-llm-flash-5layers 1x4': {'leaves': 313,
                                 'sha256': '9e6a7208ba491b51918b1e0c8cb9c2a5ccd6f9cee56fe04ef8e7494f870b45a2',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/kv_a': 'PartitionSpec(None, '
                                                                  'None)',
                                           '*/layer_*/attn/kv_b': 'PartitionSpec(None, '
                                                                  "'model')",
                                           '*/layer_*/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q_a': 'PartitionSpec(None, '
                                                                 'None)',
                                           '*/layer_*/attn/q_b': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           '*/mtp/eh': 'PartitionSpec(None, '
                                                       "'model')",
                                           '*/mtp/embed_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/hidden_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/kv_a': 'PartitionSpec(None, '
                                                                    'None)',
                                           '*/mtp/layer/attn/kv_b': 'PartitionSpec(None, '
                                                                    "'model')",
                                           '*/mtp/layer/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/o': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/mtp/layer/attn/q_a': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/mtp/layer/attn/q_b': 'PartitionSpec(None, '
                                                                   "'model')",
                                           '*/mtp/layer/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/experts/down': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/gate': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/up': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/mtp/layer/moe/router': 'PartitionSpec(None, '
                                                                     'None)',
                                           '*/mtp/layer/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/shared/down': "PartitionSpec('model', "
                                                                          'None)',
                                           '*/mtp/layer/moe/shared/gate': 'PartitionSpec(None, '
                                                                          "'model')",
                                           '*/mtp/layer/moe/shared/up': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/mtp/layer/norm1/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'joyai-llm-flash-5layers 2x4': {'leaves': 313,
                                 'sha256': '9e6a7208ba491b51918b1e0c8cb9c2a5ccd6f9cee56fe04ef8e7494f870b45a2',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/kv_a': 'PartitionSpec(None, '
                                                                  'None)',
                                           '*/layer_*/attn/kv_b': 'PartitionSpec(None, '
                                                                  "'model')",
                                           '*/layer_*/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q_a': 'PartitionSpec(None, '
                                                                 'None)',
                                           '*/layer_*/attn/q_b': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                 "'model')",
                                           '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           '*/mtp/eh': 'PartitionSpec(None, '
                                                       "'model')",
                                           '*/mtp/embed_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/hidden_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/kv_a': 'PartitionSpec(None, '
                                                                    'None)',
                                           '*/mtp/layer/attn/kv_b': 'PartitionSpec(None, '
                                                                    "'model')",
                                           '*/mtp/layer/attn/kv_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/attn/o': "PartitionSpec('model', "
                                                                 'None)',
                                           '*/mtp/layer/attn/q_a': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/mtp/layer/attn/q_b': 'PartitionSpec(None, '
                                                                   "'model')",
                                           '*/mtp/layer/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/experts/down': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/gate': "PartitionSpec('model', "
                                                                           'None, '
                                                                           'None)',
                                           '*/mtp/layer/moe/experts/up': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/mtp/layer/moe/router': 'PartitionSpec(None, '
                                                                     'None)',
                                           '*/mtp/layer/moe/router_bias': 'PartitionSpec(None,)',
                                           '*/mtp/layer/moe/shared/down': "PartitionSpec('model', "
                                                                          'None)',
                                           '*/mtp/layer/moe/shared/gate': 'PartitionSpec(None, '
                                                                          "'model')",
                                           '*/mtp/layer/moe/shared/up': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/mtp/layer/norm1/scale': 'PartitionSpec(None,)',
                                           '*/mtp/layer/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'keye-vl-2.0-30b-a3b-ep8 1x1': {'leaves': 265,
                                 'sha256': '83f2a3f2bcbf900b24e886632f11c20e5cf753cbde1368ad5865b6e254e30ab6',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/indexer/index_k': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/indexer/index_norm/bias': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_q': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/indexer/index_w': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'keye-vl-2.0-30b-a3b-ep8 1x4': {'leaves': 265,
                                 'sha256': '83f2a3f2bcbf900b24e886632f11c20e5cf753cbde1368ad5865b6e254e30ab6',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/indexer/index_k': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/indexer/index_norm/bias': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_q': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/indexer/index_w': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'keye-vl-2.0-30b-a3b-ep8 2x4': {'leaves': 265,
                                 'sha256': '83f2a3f2bcbf900b24e886632f11c20e5cf753cbde1368ad5865b6e254e30ab6',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/indexer/index_k': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/indexer/index_norm/bias': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/indexer/index_q': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/indexer/index_w': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'laguna-xs.2-33b-a3b-ep16 1x1': {'leaves': 208,
                                  'sha256': '81a4350a627b2b6f70f4e3d2a08915be8ba5ed625148fe3312a27ff8fcafeba6',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/head_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                         'None)',
                                            '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                         "'model')",
                                            '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'laguna-xs.2-33b-a3b-ep16 1x4': {'leaves': 208,
                                  'sha256': '81a4350a627b2b6f70f4e3d2a08915be8ba5ed625148fe3312a27ff8fcafeba6',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/head_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                         'None)',
                                            '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                         "'model')",
                                            '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'laguna-xs.2-33b-a3b-ep16 2x4': {'leaves': 208,
                                  'sha256': '81a4350a627b2b6f70f4e3d2a08915be8ba5ed625148fe3312a27ff8fcafeba6',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/head_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                         'None)',
                                            '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                         "'model')",
                                            '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'ouro-2.6b-8layers 1x1': {'leaves': 280,
                           'sha256': '93862d6534a2afb3cd65a7451ddf731be01856dfb6f313355f853f0afc5e6d3b',
                           'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                          'None)',
                                     '*/exit_gate/bias': 'PartitionSpec()',
                                     '*/exit_gate/kernel': 'PartitionSpec(None, '
                                                           'None)',
                                     '*/final_norm/scale': 'PartitionSpec(None,)',
                                     '*/head/kernel': 'PartitionSpec(None, '
                                                      "'model')",
                                     '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/o': "PartitionSpec('model', "
                                                         'None)',
                                     '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                           'None)',
                                     '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                           "'model')",
                                     '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm3/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm4/scale': 'PartitionSpec(None,)',
                                     'opt_state/0/count': 'PartitionSpec()'}},
 'ouro-2.6b-8layers 1x4': {'leaves': 280,
                           'sha256': '93862d6534a2afb3cd65a7451ddf731be01856dfb6f313355f853f0afc5e6d3b',
                           'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                          'None)',
                                     '*/exit_gate/bias': 'PartitionSpec()',
                                     '*/exit_gate/kernel': 'PartitionSpec(None, '
                                                           'None)',
                                     '*/final_norm/scale': 'PartitionSpec(None,)',
                                     '*/head/kernel': 'PartitionSpec(None, '
                                                      "'model')",
                                     '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/o': "PartitionSpec('model', "
                                                         'None)',
                                     '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                           'None)',
                                     '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                           "'model')",
                                     '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm3/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm4/scale': 'PartitionSpec(None,)',
                                     'opt_state/0/count': 'PartitionSpec()'}},
 'ouro-2.6b-8layers 2x4': {'leaves': 280,
                           'sha256': '93862d6534a2afb3cd65a7451ddf731be01856dfb6f313355f853f0afc5e6d3b',
                           'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                          'None)',
                                     '*/exit_gate/bias': 'PartitionSpec()',
                                     '*/exit_gate/kernel': 'PartitionSpec(None, '
                                                           'None)',
                                     '*/final_norm/scale': 'PartitionSpec(None,)',
                                     '*/head/kernel': 'PartitionSpec(None, '
                                                      "'model')",
                                     '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/o': "PartitionSpec('model', "
                                                         'None)',
                                     '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                           'None)',
                                     '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                           "'model')",
                                     '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                         "'model')",
                                     '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm3/scale': 'PartitionSpec(None,)',
                                     '*/layer_*/norm4/scale': 'PartitionSpec(None,)',
                                     'opt_state/0/count': 'PartitionSpec()'}},
 'phi-4-mini-flash-6layers 1x1': {'leaves': 277,
                                  'sha256': '1414287b07d6e9ca1568e277506189b416353257a40615e95d8adbf59d758fdc',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/bias': 'PartitionSpec(None,)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/o_bias': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/q_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/qkv': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/attn/qkv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/subln': 'PartitionSpec(None,)',
                                            '*/layer_*/gmu/gmu_in': 'PartitionSpec(None, '
                                                                    "'model')",
                                            '*/layer_*/gmu/gmu_out': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/A_log': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_kernel': 'PartitionSpec(None, '
                                                                           'None, '
                                                                           "'model')",
                                            '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/dt_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/in_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                        'None)',
                                            '*/layer_*/mamba/x_proj': "PartitionSpec('model', "
                                                                      'None)',
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/norm1/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'phi-4-mini-flash-6layers 1x4': {'leaves': 277,
                                  'sha256': '1414287b07d6e9ca1568e277506189b416353257a40615e95d8adbf59d758fdc',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/bias': 'PartitionSpec(None,)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/o_bias': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/q_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/qkv': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/attn/qkv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/subln': 'PartitionSpec(None,)',
                                            '*/layer_*/gmu/gmu_in': 'PartitionSpec(None, '
                                                                    "'model')",
                                            '*/layer_*/gmu/gmu_out': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/A_log': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_kernel': 'PartitionSpec(None, '
                                                                           'None, '
                                                                           "'model')",
                                            '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/dt_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/in_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                        'None)',
                                            '*/layer_*/mamba/x_proj': "PartitionSpec('model', "
                                                                      'None)',
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/norm1/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'phi-4-mini-flash-6layers 2x4': {'leaves': 277,
                                  'sha256': '1414287b07d6e9ca1568e277506189b416353257a40615e95d8adbf59d758fdc',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/bias': 'PartitionSpec(None,)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_k2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q1': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/lambda_q2': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/o_bias': 'PartitionSpec(None,)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/q_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/qkv': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/attn/qkv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/attn/subln': 'PartitionSpec(None,)',
                                            '*/layer_*/gmu/gmu_in': 'PartitionSpec(None, '
                                                                    "'model')",
                                            '*/layer_*/gmu/gmu_out': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/A_log': "PartitionSpec('model', "
                                                                     'None)',
                                            '*/layer_*/mamba/D': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/conv_kernel': 'PartitionSpec(None, '
                                                                           'None, '
                                                                           "'model')",
                                            '*/layer_*/mamba/dt_bias': "PartitionSpec('model',)",
                                            '*/layer_*/mamba/dt_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/in_proj': 'PartitionSpec(None, '
                                                                       "'model')",
                                            '*/layer_*/mamba/out_proj': "PartitionSpec('model', "
                                                                        'None)',
                                            '*/layer_*/mamba/x_proj': "PartitionSpec('model', "
                                                                      'None)',
                                            '*/layer_*/mlp/down': "PartitionSpec('model', "
                                                                  'None)',
                                            '*/layer_*/mlp/gate': 'PartitionSpec(None, '
                                                                  "'model')",
                                            '*/layer_*/mlp/up': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/norm1/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/bias': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'qwen3-next-80b-a3b-ep16 1x1': {'leaves': 211,
                                 'sha256': 'cffb3697ec8b15ff14741ac6ebed2faa2d9c364bd148339f34641dda2994efb1',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/delta/A_log': "PartitionSpec('model',)",
                                           '*/layer_*/delta/conv': 'PartitionSpec(None, '
                                                                   'None, '
                                                                   "'model')",
                                           '*/layer_*/delta/dt_bias': "PartitionSpec('model',)",
                                           '*/layer_*/delta/in_proj_ba': 'PartitionSpec(None, '
                                                                         'None)',
                                           '*/layer_*/delta/in_proj_qkvz': 'PartitionSpec(None, '
                                                                           "'model')",
                                           '*/layer_*/delta/norm_scale': 'PartitionSpec(None,)',
                                           '*/layer_*/delta/out_proj': "PartitionSpec('model', "
                                                                       'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/moe/shared_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'qwen3-next-80b-a3b-ep16 1x4': {'leaves': 211,
                                 'sha256': 'cffb3697ec8b15ff14741ac6ebed2faa2d9c364bd148339f34641dda2994efb1',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/delta/A_log': "PartitionSpec('model',)",
                                           '*/layer_*/delta/conv': 'PartitionSpec(None, '
                                                                   'None, '
                                                                   "'model')",
                                           '*/layer_*/delta/dt_bias': "PartitionSpec('model',)",
                                           '*/layer_*/delta/in_proj_ba': 'PartitionSpec(None, '
                                                                         'None)',
                                           '*/layer_*/delta/in_proj_qkvz': 'PartitionSpec(None, '
                                                                           "'model')",
                                           '*/layer_*/delta/norm_scale': 'PartitionSpec(None,)',
                                           '*/layer_*/delta/out_proj': "PartitionSpec('model', "
                                                                       'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/moe/shared_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'qwen3-next-80b-a3b-ep16 2x4': {'leaves': 211,
                                 'sha256': 'cffb3697ec8b15ff14741ac6ebed2faa2d9c364bd148339f34641dda2994efb1',
                                 'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                'None)',
                                           '*/final_norm/scale': 'PartitionSpec(None,)',
                                           '*/head/kernel': 'PartitionSpec(None, '
                                                            "'model')",
                                           '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/k_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/o': "PartitionSpec('model', "
                                                               'None)',
                                           '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/attn/q_norm/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                               "'model')",
                                           '*/layer_*/delta/A_log': "PartitionSpec('model',)",
                                           '*/layer_*/delta/conv': 'PartitionSpec(None, '
                                                                   'None, '
                                                                   "'model')",
                                           '*/layer_*/delta/dt_bias': "PartitionSpec('model',)",
                                           '*/layer_*/delta/in_proj_ba': 'PartitionSpec(None, '
                                                                         'None)',
                                           '*/layer_*/delta/in_proj_qkvz': 'PartitionSpec(None, '
                                                                           "'model')",
                                           '*/layer_*/delta/norm_scale': 'PartitionSpec(None,)',
                                           '*/layer_*/delta/out_proj': "PartitionSpec('model', "
                                                                       'None)',
                                           '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                         'None, '
                                                                         'None)',
                                           '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                       'None, '
                                                                       'None)',
                                           '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                   'None)',
                                           '*/layer_*/moe/shared/down': "PartitionSpec('model', "
                                                                        'None)',
                                           '*/layer_*/moe/shared/gate': 'PartitionSpec(None, '
                                                                        "'model')",
                                           '*/layer_*/moe/shared/up': 'PartitionSpec(None, '
                                                                      "'model')",
                                           '*/layer_*/moe/shared_gate': 'PartitionSpec(None, '
                                                                        'None)',
                                           '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                           '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                           'opt_state/0/count': 'PartitionSpec()'}},
 'smallthinker-21b-a3b-ep4 1x1': {'leaves': 130,
                                  'sha256': 'ebc5536e29270090b453c46d93b208ce27e3d663d902832ac38fec90a6d72bee',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'smallthinker-21b-a3b-ep4 1x4': {'leaves': 130,
                                  'sha256': 'ebc5536e29270090b453c46d93b208ce27e3d663d902832ac38fec90a6d72bee',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'smallthinker-21b-a3b-ep4 2x4': {'leaves': 130,
                                  'sha256': 'ebc5536e29270090b453c46d93b208ce27e3d663d902832ac38fec90a6d72bee',
                                  'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                                 'None)',
                                            '*/final_norm/scale': 'PartitionSpec(None,)',
                                            '*/head/kernel': 'PartitionSpec(None, '
                                                             "'model')",
                                            '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/o': "PartitionSpec('model', "
                                                                'None)',
                                            '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                                "'model')",
                                            '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                                          'None, '
                                                                          'None)',
                                            '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                                        'None, '
                                                                        'None)',
                                            '*/layer_*/moe/router': 'PartitionSpec(None, '
                                                                    'None)',
                                            '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                            '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                            'opt_state/0/count': 'PartitionSpec()'}},
 'zaya1-8b-ep2 1x1': {'leaves': 382,
                      'sha256': '0ab9cc82f625b77a607e18b6073d8006b9f65bebd42100d83820b29e007fe767',
                      'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                     'None)',
                                '*/final_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_head': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/attn/conv_head_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_time': 'PartitionSpec(None, '
                                                            'None, None)',
                                '*/layer_*/attn/conv_time_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/o': "PartitionSpec('model', "
                                                    'None)',
                                '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/temperature': 'PartitionSpec(None,)',
                                '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_down': 'PartitionSpec(None, '
                                                             'None)',
                                '*/layer_*/moe/router_down_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b1': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b2': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b3': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/w1': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w2': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w3': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_state': 'PartitionSpec(None,)',
                                '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                'opt_state/0/count': 'PartitionSpec()'}},
 'zaya1-8b-ep2 1x4': {'leaves': 382,
                      'sha256': '0ab9cc82f625b77a607e18b6073d8006b9f65bebd42100d83820b29e007fe767',
                      'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                     'None)',
                                '*/final_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_head': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/attn/conv_head_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_time': 'PartitionSpec(None, '
                                                            'None, None)',
                                '*/layer_*/attn/conv_time_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/o': "PartitionSpec('model', "
                                                    'None)',
                                '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/temperature': 'PartitionSpec(None,)',
                                '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_down': 'PartitionSpec(None, '
                                                             'None)',
                                '*/layer_*/moe/router_down_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b1': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b2': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b3': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/w1': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w2': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w3': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_state': 'PartitionSpec(None,)',
                                '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                'opt_state/0/count': 'PartitionSpec()'}},
 'zaya1-8b-ep2 2x4': {'leaves': 382,
                      'sha256': '0ab9cc82f625b77a607e18b6073d8006b9f65bebd42100d83820b29e007fe767',
                      'specs': {'*/embed/embedding': "PartitionSpec('model', "
                                                     'None)',
                                '*/final_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_head': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/attn/conv_head_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/conv_time': 'PartitionSpec(None, '
                                                            'None, None)',
                                '*/layer_*/attn/conv_time_bias': 'PartitionSpec(None,)',
                                '*/layer_*/attn/k': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/o': "PartitionSpec('model', "
                                                    'None)',
                                '*/layer_*/attn/q': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/attn/temperature': 'PartitionSpec(None,)',
                                '*/layer_*/attn/v': 'PartitionSpec(None, '
                                                    "'model')",
                                '*/layer_*/moe/experts/down': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/gate': "PartitionSpec('model', "
                                                              'None, None)',
                                '*/layer_*/moe/experts/up': "PartitionSpec('model', "
                                                            'None, None)',
                                '*/layer_*/moe/router_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_down': 'PartitionSpec(None, '
                                                             'None)',
                                '*/layer_*/moe/router_down_bias': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b1': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b2': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/b3': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_mlp/w1': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w2': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_mlp/w3': 'PartitionSpec(None, '
                                                               'None)',
                                '*/layer_*/moe/router_norm/scale': 'PartitionSpec(None,)',
                                '*/layer_*/moe/router_state': 'PartitionSpec(None,)',
                                '*/layer_*/norm1/scale': 'PartitionSpec(None,)',
                                '*/layer_*/norm2/scale': 'PartitionSpec(None,)',
                                'opt_state/0/count': 'PartitionSpec()'}}}

BUILDS = {'cca_moe': {'config': {'algorithm': 'ES',
                        'attention_form': 'xla',
                        'attention_form_by_kind': 'causal:xla',
                        'attention_form_why': "the devices are 'cpu', not "
                                              'TPUs',
                        'backend': 'device',
                        'centre_bytes_per_chip': 77420,
                        'centre_form': 'split',
                        'centre_form_why': "the mesh's model axis is 1: every "
                                           'leaf is whole on its chip',
                        'combine_form': 'xla',
                        'compute_dtype': 'bfloat16',
                        'conv_taps': 4,
                        'delta_form': None,
                        'experts_held': 2,
                        'experts_per_token': 1,
                        'experts_total': 4,
                        'forward_form': 'perturbed',
                        'head_form': 'xla',
                        'head_form_why': "the devices are 'cpu', not TPUs",
                        'latent_kv_width': 16,
                        'latent_q_width': 64,
                        'low_rank': 1,
                        'mesh_axes': {'model': 1, 'pop': 1},
                        'mirrored': True,
                        'mtp_depth': 0,
                        'noise_gather_form': None,
                        'noise_mode': 'table',
                        'noise_rows_per_generation': 4,
                        'obs_norm': False,
                        'population_size': 8,
                        'router_hidden': 16,
                        'scan_form': None,
                        'seed': 0,
                        'shard_params': True,
                        'sigma': 0.02,
                        'tokens_per_generation': 168},
             'gauges': {'attention_form': 'xla',
                        'attention_form_by_kind': 'causal:xla',
                        'centre_bytes_per_chip': 77420,
                        'centre_form': 'split',
                        'centre_form_why': "the mesh's model axis is 1: every "
                                           'leaf is whole on its chip',
                        'combine_form': 'xla',
                        'conv_taps': 4,
                        'experts_held': 2,
                        'experts_per_token': 1,
                        'experts_total': 4,
                        'forward_form': 'perturbed',
                        'head_form': 'xla',
                        'latent_kv_width': 16,
                        'latent_q_width': 64,
                        'mesh_shape': '1x1',
                        'mtp_depth': 0,
                        'noise_rows_per_generation': 4,
                        'param_bytes_per_chip': 140728,
                        'router_hidden': 16,
                        'tokens_per_generation': 168},
             'sized': {'eval_chunk': 8,
                       'factored_leaves': 25,
                       'float32_leaves_kept': 33,
                       'n_eval_chunks': 1,
                       'pair_chunk': 4,
                       'selection_bytes': 0,
                       'signs_in_turn': False}},
 'delta_moe': {'config': {'algorithm': 'ES',
                          'attention_form': 'xla',
                          'attention_form_by_kind': 'causal:xla',
                          'attention_form_why': "the devices are 'cpu', not "
                                                'TPUs',
                          'backend': 'device',
                          'centre_bytes_per_chip': 122896,
                          'centre_form': 'split',
                          'centre_form_why': "the mesh's model axis is 1: "
                                             'every leaf is whole on its chip',
                          'combine_form': 'xla',
                          'compute_dtype': 'bfloat16',
                          'delta_chunk': 8,
                          'delta_form': 'xla',
                          'delta_inverse': 'blocks of 8 by the finite product '
                                           '(I - A)(I + A^2)(I + A^4)..., '
                                           'merged in pairs',
                          'experts_held': 4,
                          'experts_per_token': 3,
                          'experts_total': 16,
                          'forward_form': 'perturbed',
                          'full_layers': 1,
                          'head_form': 'xla',
                          'head_form_why': "the devices are 'cpu', not TPUs",
                          'linear_layers': 3,
                          'low_rank': 1,
                          'mesh_axes': {'model': 1, 'pop': 1},
                          'mirrored': True,
                          'mtp_depth': 0,
                          'noise_gather_form': None,
                          'noise_mode': 'table',
                          'noise_rows_per_generation': 4,
                          'obs_norm': False,
                          'population_size': 8,
                          'scan_form': None,
                          'seed': 0,
                          'shard_params': True,
                          'sigma': 0.02,
                          'tokens_per_generation': 168},
               'gauges': {'attention_form': 'xla',
                          'attention_form_by_kind': 'causal:xla',
                          'centre_bytes_per_chip': 122896,
                          'centre_form': 'split',
                          'centre_form_why': "the mesh's model axis is 1: "
                                             'every leaf is whole on its chip',
                          'combine_form': 'xla',
                          'delta_chunk': 8,
                          'delta_form': 'xla',
                          'delta_inverse': 'blocks of 8 by the finite product '
                                           '(I - A)(I + A^2)(I + A^4)..., '
                                           'merged in pairs',
                          'experts_held': 4,
                          'experts_per_token': 3,
                          'experts_total': 16,
                          'forward_form': 'perturbed',
                          'full_layers': 1,
                          'head_form': 'xla',
                          'linear_layers': 3,
                          'mesh_shape': '1x1',
                          'mtp_depth': 0,
                          'noise_rows_per_generation': 4,
                          'param_bytes_per_chip': 237504,
                          'tokens_per_generation': 168},
               'sized': {'eval_chunk': 8,
                         'factored_leaves': 31,
                         'float32_leaves_kept': 10,
                         'n_eval_chunks': 1,
                         'pair_chunk': 4,
                         'selection_bytes': 0,
                         'signs_in_turn': False}},
 'gated_window_moe': {'config': {'algorithm': 'ES',
                                 'attention_form': 'xla',
                                 'attention_form_by_kind': 'sliding:xla,full:xla',
                                 'attention_form_why': 'the devices are '
                                                       "'cpu', not TPUs",
                                 'backend': 'device',
                                 'centre_bytes_per_chip': 100160,
                                 'centre_form': 'split',
                                 'centre_form_why': "the mesh's model axis is "
                                                    '1: every leaf is whole '
                                                    'on its chip',
                                 'combine_form': 'xla',
                                 'compute_dtype': 'bfloat16',
                                 'delta_form': None,
                                 'dense_layers': 1,
                                 'experts_held': 4,
                                 'experts_per_token': 3,
                                 'experts_total': 16,
                                 'forward_form': 'perturbed',
                                 'full_heads': 4,
                                 'full_layers': 2,
                                 'head_form': 'xla',
                                 'head_form_why': "the devices are 'cpu', not "
                                                  'TPUs',
                                 'low_rank': 1,
                                 'mesh_axes': {'model': 1, 'pop': 1},
                                 'mirrored': True,
                                 'mtp_depth': 0,
                                 'noise_gather_form': None,
                                 'noise_mode': 'table',
                                 'noise_rows_per_generation': 4,
                                 'obs_norm': False,
                                 'population_size': 8,
                                 'scan_form': None,
                                 'seed': 0,
                                 'shard_params': True,
                                 'sigma': 0.02,
                                 'sliding_heads': 6,
                                 'sliding_layers': 2,
                                 'sliding_window': 6,
                                 'tokens_per_generation': 168},
                      'gauges': {'attention_form': 'xla',
                                 'attention_form_by_kind': 'sliding:xla,full:xla',
                                 'centre_bytes_per_chip': 100160,
                                 'centre_form': 'split',
                                 'centre_form_why': "the mesh's model axis is "
                                                    '1: every leaf is whole '
                                                    'on its chip',
                                 'combine_form': 'xla',
                                 'dense_layers': 1,
                                 'experts_held': 4,
                                 'experts_per_token': 3,
                                 'experts_total': 16,
                                 'forward_form': 'perturbed',
                                 'full_heads': 4,
                                 'full_layers': 2,
                                 'head_form': 'xla',
                                 'mesh_shape': '1x1',
                                 'mtp_depth': 0,
                                 'noise_rows_per_generation': 4,
                                 'param_bytes_per_chip': 194176,
                                 'sliding_heads': 6,
                                 'sliding_layers': 2,
                                 'sliding_window': 6,
                                 'tokens_per_generation': 168},
                      'sized': {'eval_chunk': 8,
                                'factored_leaves': 37,
                                'float32_leaves_kept': 3,
                                'n_eval_chunks': 1,
                                'pair_chunk': 4,
                                'selection_bytes': 0,
                                'signs_in_turn': False}},
 'hybrid': {'config': {'algorithm': 'ES',
                       'attention_form': 'xla',
                       'attention_form_by_kind': 'causal:xla',
                       'attention_form_why': "the devices are 'cpu', not TPUs",
                       'backend': 'device',
                       'centre_bytes_per_chip': 65904,
                       'centre_form': 'gathered',
                       'centre_form_why': 'the centre in its compute dtypes, '
                                          '65904 bytes, fits a chip of '
                                          '16000000000 bytes beside the '
                                          '268790900 it holds: whole members '
                                          "on each of the mesh's chips, no "
                                          "'model' axis in the forward",
                       'combine_form': None,
                       'compute_dtype': 'bfloat16',
                       'delta_form': None,
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'head_form_why': "the devices are 'cpu', not TPUs",
                       'low_rank': 1,
                       'mesh_axes': {'model': 2, 'pop': 2},
                       'mirrored': True,
                       'noise_gather_form': None,
                       'noise_mode': 'table',
                       'noise_rows_per_generation': 4,
                       'obs_norm': False,
                       'population_size': 8,
                       'scan_form': None,
                       'seed': 0,
                       'shard_params': True,
                       'sigma': 0.02,
                       'tokens_per_generation': 168},
            'gauges': {'attention_form': 'xla',
                       'attention_form_by_kind': 'causal:xla',
                       'centre_bytes_per_chip': 65904,
                       'centre_form': 'gathered',
                       'centre_form_why': 'the centre in its compute dtypes, '
                                          '65904 bytes, fits a chip of '
                                          '16000000000 bytes beside the '
                                          '268790900 it holds: whole members '
                                          "on each of the mesh's chips, no "
                                          "'model' axis in the forward",
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'mesh_shape': '2x2',
                       'noise_rows_per_generation': 4,
                       'param_bytes_per_chip': 71088,
                       'tokens_per_generation': 168},
            'sized': {'eval_chunk': 8,
                      'factored_leaves': 24,
                      'float32_leaves_kept': 0,
                      'n_eval_chunks': 1,
                      'pair_chunk': 4,
                      'selection_bytes': 0,
                      'signs_in_turn': False}},
 'indexed_moe': {'config': {'algorithm': 'ES',
                            'attention_form': 'xla',
                            'attention_form_by_kind': 'selected:xla',
                            'attention_form_why': "the devices are 'cpu', not "
                                                  'TPUs',
                            'backend': 'device',
                            'centre_bytes_per_chip': 53248,
                            'centre_form': 'split',
                            'centre_form_why': "the mesh's model axis is 1: "
                                               'every leaf is whole on its '
                                               'chip',
                            'combine_form': 'xla',
                            'compute_dtype': 'bfloat16',
                            'delta_form': None,
                            'experts_held': 4,
                            'experts_per_token': 3,
                            'experts_total': 16,
                            'forward_form': 'perturbed',
                            'head_form': 'xla',
                            'head_form_why': "the devices are 'cpu', not TPUs",
                            'index_head_dim': 8,
                            'index_heads': 2,
                            'low_rank': 1,
                            'mesh_axes': {'model': 1, 'pop': 1},
                            'mirrored': True,
                            'mtp_depth': 0,
                            'noise_gather_form': None,
                            'noise_mode': 'table',
                            'noise_rows_per_generation': 4,
                            'obs_norm': False,
                            'population_size': 8,
                            'position_streams': 3,
                            'scan_form': None,
                            'seed': 0,
                            'shard_params': True,
                            'sigma': 0.02,
                            'sparse_topk': 6,
                            'tokens_per_generation': 168},
                 'gauges': {'attention_form': 'xla',
                            'attention_form_by_kind': 'selected:xla',
                            'centre_bytes_per_chip': 53248,
                            'centre_form': 'split',
                            'centre_form_why': "the mesh's model axis is 1: "
                                               'every leaf is whole on its '
                                               'chip',
                            'combine_form': 'xla',
                            'experts_held': 4,
                            'experts_per_token': 3,
                            'experts_total': 16,
                            'forward_form': 'perturbed',
                            'head_form': 'xla',
                            'index_head_dim': 8,
                            'index_heads': 2,
                            'mesh_shape': '1x1',
                            'mtp_depth': 0,
                            'noise_rows_per_generation': 4,
                            'param_bytes_per_chip': 101760,
                            'position_streams': 3,
                            'sparse_topk': 6,
                            'tokens_per_generation': 168},
                 'sized': {'eval_chunk': 8,
                           'factored_leaves': 18,
                           'float32_leaves_kept': 8,
                           'n_eval_chunks': 1,
                           'pair_chunk': 4,
                           'selection_bytes': 1785,
                           'signs_in_turn': False}},
 'looped': {'config': {'algorithm': 'ES',
                       'attention_form': 'xla',
                       'attention_form_by_kind': 'causal:xla',
                       'attention_form_why': "the devices are 'cpu', not TPUs",
                       'backend': 'device',
                       'centre_bytes_per_chip': 39554,
                       'centre_form': 'split',
                       'centre_form_why': "the mesh's model axis is 1: every "
                                          'leaf is whole on its chip',
                       'combine_form': None,
                       'compute_dtype': 'bfloat16',
                       'delta_form': None,
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'head_form_why': "the devices are 'cpu', not TPUs",
                       'layer_applications_per_token': 8,
                       'loop_steps': 4,
                       'low_rank': 1,
                       'mesh_axes': {'model': 1, 'pop': 1},
                       'mirrored': True,
                       'noise_gather_form': None,
                       'noise_mode': 'table',
                       'noise_rows_per_generation': 4,
                       'obs_norm': False,
                       'population_size': 8,
                       'scan_form': None,
                       'seed': 0,
                       'shard_params': True,
                       'sigma': 0.02,
                       'tokens_per_generation': 168},
            'gauges': {'attention_form': 'xla',
                       'attention_form_by_kind': 'causal:xla',
                       'centre_bytes_per_chip': 39554,
                       'centre_form': 'split',
                       'centre_form_why': "the mesh's model axis is 1: every "
                                          'leaf is whole on its chip',
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'layer_applications_per_token': 8,
                       'loop_steps': 4,
                       'mesh_shape': '1x1',
                       'noise_rows_per_generation': 4,
                       'param_bytes_per_chip': 79108,
                       'tokens_per_generation': 168},
            'sized': {'eval_chunk': 8,
                      'factored_leaves': 16,
                      'float32_leaves_kept': 0,
                      'n_eval_chunks': 1,
                      'pair_chunk': 4,
                      'selection_bytes': 0,
                      'signs_in_turn': False}},
 'mlp_replicated': {'config': {'algorithm': 'ES',
                               'attention_form': None,
                               'attention_form_by_kind': None,
                               'attention_form_why': None,
                               'backend': 'device',
                               'combine_form': None,
                               'compute_dtype': 'float32',
                               'delta_form': None,
                               'forward_form': 'pair_shared',
                               'head_form': None,
                               'head_form_why': None,
                               'low_rank': 0,
                               'mirrored': True,
                               'noise_gather_form': 'slice',
                               'noise_rows_per_generation': 4,
                               'obs_norm': False,
                               'population_size': 8,
                               'scan_form': None,
                               'seed': 0,
                               'shard_params': False,
                               'sigma': 0.1},
                    'gauges': {'forward_form': 'pair_shared',
                               'noise_gather_form': 'slice',
                               'noise_rows_per_generation': 4},
                    'sized': {'eval_chunk': 2}},
 'mlp_sharded': {'config': {'algorithm': 'ES',
                            'attention_form': None,
                            'attention_form_by_kind': None,
                            'attention_form_why': None,
                            'backend': 'device',
                            'centre_bytes_per_chip': 772,
                            'centre_form': 'split',
                            'centre_form_why': 'the materialised form builds '
                                               "members' weights, a shard a "
                                               'chip',
                            'combine_form': None,
                            'compute_dtype': 'float32',
                            'delta_form': None,
                            'forward_form': 'materialised',
                            'head_form': None,
                            'head_form_why': None,
                            'low_rank': 0,
                            'mesh_axes': {'model': 2, 'pop': 2},
                            'mirrored': True,
                            'noise_gather_form': None,
                            'noise_mode': 'program',
                            'noise_rows_per_generation': 4,
                            'obs_norm': False,
                            'population_size': 8,
                            'scan_form': None,
                            'seed': 0,
                            'shard_params': True,
                            'sigma': 0.1},
                 'gauges': {'centre_bytes_per_chip': 772,
                            'centre_form': 'split',
                            'centre_form_why': 'the materialised form builds '
                                               "members' weights, a shard a "
                                               'chip',
                            'forward_form': 'materialised',
                            'mesh_shape': '2x2',
                            'noise_rows_per_generation': 4,
                            'param_bytes_per_chip': 772},
                 'sized': {'eval_chunk': 8,
                           'factored_leaves': 0,
                           'float32_leaves_kept': 6,
                           'n_eval_chunks': 1,
                           'selection_bytes': 0}},
 'moe': {'config': {'algorithm': 'ES',
                    'attention_form': 'xla',
                    'attention_form_by_kind': 'causal:xla',
                    'attention_form_why': "the devices are 'cpu', not TPUs",
                    'backend': 'device',
                    'centre_bytes_per_chip': 97920,
                    'centre_form': 'split',
                    'centre_form_why': "the mesh's model axis is 1: every "
                                       'leaf is whole on its chip',
                    'combine_form': 'xla',
                    'compute_dtype': 'bfloat16',
                    'delta_form': None,
                    'experts_held': 4,
                    'experts_per_token': 3,
                    'experts_total': 16,
                    'forward_form': 'perturbed',
                    'head_form': 'xla',
                    'head_form_why': "the devices are 'cpu', not TPUs",
                    'low_rank': 1,
                    'mesh_axes': {'model': 1, 'pop': 1},
                    'mirrored': True,
                    'mtp_depth': 1,
                    'noise_gather_form': None,
                    'noise_mode': 'table',
                    'noise_rows_per_generation': 4,
                    'obs_norm': False,
                    'population_size': 8,
                    'scan_form': None,
                    'seed': 0,
                    'shard_params': True,
                    'sigma': 0.02,
                    'tokens_per_generation': 168},
         'gauges': {'attention_form': 'xla',
                    'attention_form_by_kind': 'causal:xla',
                    'centre_bytes_per_chip': 97920,
                    'centre_form': 'split',
                    'centre_form_why': "the mesh's model axis is 1: every "
                                       'leaf is whole on its chip',
                    'combine_form': 'xla',
                    'experts_held': 4,
                    'experts_per_token': 3,
                    'experts_total': 16,
                    'forward_form': 'perturbed',
                    'head_form': 'xla',
                    'mesh_shape': '1x1',
                    'mtp_depth': 1,
                    'noise_rows_per_generation': 4,
                    'param_bytes_per_chip': 189504,
                    'tokens_per_generation': 168},
         'sized': {'eval_chunk': 8,
                   'factored_leaves': 38,
                   'float32_leaves_kept': 6,
                   'n_eval_chunks': 1,
                   'pair_chunk': 4,
                   'selection_bytes': 0,
                   'signs_in_turn': False}},
 'sambay': {'config': {'algorithm': 'ES',
                       'attention_form': 'xla',
                       'attention_form_by_kind': 'window:xla,full_kv:xla,cross:xla',
                       'attention_form_why': "the devices are 'cpu', not TPUs",
                       'backend': 'device',
                       'centre_bytes_per_chip': 120176,
                       'centre_form': 'split',
                       'centre_form_why': "the mesh's model axis is 1: every "
                                          'leaf is whole on its chip',
                       'combine_form': None,
                       'compute_dtype': 'bfloat16',
                       'delta_form': None,
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'head_form_why': "the devices are 'cpu', not TPUs",
                       'kv_shared_by': 1,
                       'layer_kinds': 'mamba,window,mamba_mem,full_kv,gmu,cross',
                       'low_rank': 1,
                       'memory_shared_by': 1,
                       'mesh_axes': {'model': 1, 'pop': 1},
                       'mirrored': True,
                       'noise_gather_form': None,
                       'noise_mode': 'table',
                       'noise_rows_per_generation': 4,
                       'obs_norm': False,
                       'population_size': 8,
                       'scan_chunk': 4,
                       'scan_form': 'xla',
                       'seed': 0,
                       'shard_params': True,
                       'sigma': 0.02,
                       'tokens_per_generation': 168,
                       'window': 5},
            'gauges': {'attention_form': 'xla',
                       'attention_form_by_kind': 'window:xla,full_kv:xla,cross:xla',
                       'centre_bytes_per_chip': 120176,
                       'centre_form': 'split',
                       'centre_form_why': "the mesh's model axis is 1: every "
                                          'leaf is whole on its chip',
                       'forward_form': 'perturbed',
                       'head_form': 'xla',
                       'kv_shared_by': 1,
                       'layer_kinds': 'mamba,window,mamba_mem,full_kv,gmu,cross',
                       'memory_shared_by': 1,
                       'mesh_shape': '1x1',
                       'noise_rows_per_generation': 4,
                       'param_bytes_per_chip': 234528,
                       'scan_chunk': 4,
                       'scan_form': 'xla',
                       'tokens_per_generation': 168,
                       'window': 5},
            'sized': {'eval_chunk': 8,
                      'factored_leaves': 35,
                      'float32_leaves_kept': 22,
                      'n_eval_chunks': 1,
                      'pair_chunk': 4,
                      'selection_bytes': 0,
                      'signs_in_turn': False}},
 'window_moe': {'config': {'algorithm': 'ES',
                           'attention_form': 'xla',
                           'attention_form_by_kind': 'window:xla,global:xla',
                           'attention_form_why': "the devices are 'cpu', not "
                                                 'TPUs',
                           'backend': 'device',
                           'centre_bytes_per_chip': 76224,
                           'centre_form': 'split',
                           'centre_form_why': "the mesh's model axis is 1: "
                                              'every leaf is whole on its '
                                              'chip',
                           'combine_form': 'xla',
                           'compute_dtype': 'bfloat16',
                           'delta_form': None,
                           'experts_held': 4,
                           'experts_per_token': 3,
                           'experts_total': 16,
                           'forward_form': 'perturbed',
                           'global_layers': 1,
                           'head_form': 'xla',
                           'head_form_why': "the devices are 'cpu', not TPUs",
                           'low_rank': 1,
                           'mesh_axes': {'model': 1, 'pop': 1},
                           'mirrored': True,
                           'mtp_depth': 0,
                           'noise_gather_form': None,
                           'noise_mode': 'table',
                           'noise_rows_per_generation': 4,
                           'obs_norm': False,
                           'population_size': 8,
                           'scan_form': None,
                           'seed': 0,
                           'shard_params': True,
                           'sigma': 0.02,
                           'sliding_window': 6,
                           'tokens_per_generation': 168,
                           'window_layers': 2},
                'gauges': {'attention_form': 'xla',
                           'attention_form_by_kind': 'window:xla,global:xla',
                           'centre_bytes_per_chip': 76224,
                           'centre_form': 'split',
                           'centre_form_why': "the mesh's model axis is 1: "
                                              'every leaf is whole on its '
                                              'chip',
                           'combine_form': 'xla',
                           'experts_held': 4,
                           'experts_per_token': 3,
                           'experts_total': 16,
                           'forward_form': 'perturbed',
                           'global_layers': 1,
                           'head_form': 'xla',
                           'mesh_shape': '1x1',
                           'mtp_depth': 0,
                           'noise_rows_per_generation': 4,
                           'param_bytes_per_chip': 146304,
                           'sliding_window': 6,
                           'tokens_per_generation': 168,
                           'window_layers': 2},
                'sized': {'eval_chunk': 8,
                          'factored_leaves': 17,
                          'float32_leaves_kept': 3,
                          'n_eval_chunks': 1,
                          'pair_chunk': 4,
                          'selection_bytes': 0,
                          'signs_in_turn': False}}}

# ``partition_rules`` of a manifest the parent wrote: its ONE global list
RULES_JSON = [['embed/embedding$', ['model', None]],
 ['mamba/(in_z|in_x|in_dt)$', [None, 'model']],
 ['mamba/conv_x_kernel$', [None, None, 'model']],
 ['mamba/(conv_x_bias|A_log|D|dt_bias|norm_scale)$', ['model']],
 ['mamba/(in_bc|conv_bc_kernel|conv_bc_bias)$', []],
 ['mamba/out_proj$', ['model', None]],
 ['attn/(q|k|v)$', [None, 'model']],
 ['attn/o$', ['model', None]],
 ['mlp/(gate|up)$', [None, 'model']],
 ['mlp/down$', ['model', None]],
 ['(norm[1-4]|final_norm)/scale$', []],
 ['head/kernel$', [None, 'model']],
 ['exit_gate/(kernel|bias)$', []],
 ['experts/(gate|up|down)$', ['model', None, None]],
 ['shared/(gate|up)$', [None, 'model']],
 ['shared/down$', ['model', None]],
 ['attn/(q_b|kv_b)$', [None, 'model']],
 ['attn/(q_a|kv_a)$', []],
 ['(q_norm|kv_norm|embed_norm|hidden_norm)/scale$', []],
 ['moe/(router|router_bias)$', []],
 ['mtp/eh$', [None, 'model']],
 ['mamba/in_proj$', [None, 'model']],
 ['mamba/conv_kernel$', [None, None, 'model']],
 ['mamba/conv_bias$', ['model']],
 ['mamba/x_proj$', ['model', None]],
 ['mamba/dt_proj$', [None, 'model']],
 ['attn/qkv$', [None, 'model']],
 ['attn/(qkv_bias|q_bias)$', ['model']],
 ['attn/(o_bias|subln|lambda_[qk][12])$', []],
 ['gmu/gmu_in$', [None, 'model']],
 ['gmu/gmu_out$', ['model', None]],
 ['(norm[1-4]|final_norm)/bias$', []],
 ['indexer/index_q$', [None, 'model']],
 ['indexer/(index_k|index_w)$', []],
 ['indexer/index_norm/(scale|bias)$', []],
 ['k_norm/scale$', []],
 ['attn/conv_head$', ['model', None, None]],
 ['attn/(conv_time|conv_time_bias|conv_head_bias|temperature)$', []],
 ['moe/(router_down|router_down_bias|router_state)$', []],
 ['moe/router_norm/scale$', []],
 ['moe/router_mlp/[wb][123]$', []],
 ['delta/in_proj_qkvz$', [None, 'model']],
 ['delta/conv$', [None, None, 'model']],
 ['delta/(A_log|dt_bias)$', ['model']],
 ['delta/(in_proj_ba|norm_scale)$', []],
 ['delta/out_proj$', ['model', None]],
 ['moe/shared_gate$', []],
 ['attn/head_gate$', []],
 ['conv[^/]*/kernel$', [None, None, None, 'model']],
 ['kernel$', [None, 'model']],
 ['(bias|scale|embedding|carry0[^/]*)$', ['model']],
 ['.*', []]]
